"""The native codec's context outlives the pass (PR 41).

store/native_decode.py shared_context keeps the C context on the node
table's memo (state/nodes.py NodeDerived, kind codec_ctx), keyed by what
build_context reads beside the table; a pass's own plugin-ran /
score-skip rows stay on its cw (pass_rows).  Held here:

  * consecutive passes on an unchanged node list decode through ONE
    context, byte-equal to a fresh context's and the Python encoder's;
  * passes whose pods skip different plugins share it and each renders
    its own pattern (nothing of a pass is on the shared object);
  * any node change makes a new context and the old one is freed once no
    handle holds it; an in-flight chunk handle still takes its strs;
  * other weights, another scorer set, a custom plugin's other messages
    are another key: a new generation with the new bytes;
  * a lineup the LUTs cannot express is carried as None, probed once a
    table, as is a build that raises or finds no library;
    KSS_TPU_DISABLE_NATIVE=1 never touches the memo;
  * two threads decoding two passes at once give the serial bytes.
"""

from __future__ import annotations

import copy
import gc
import os
import sys
import threading
import weakref

import pytest

from kube_scheduler_simulator_tpu.framework.replay import replay
from kube_scheduler_simulator_tpu.models.workloads import make_nodes, make_pods
from kube_scheduler_simulator_tpu.native import get_lib
from kube_scheduler_simulator_tpu.plugins.custom import CustomPlugin
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.state.compile import compile_workload
from kube_scheduler_simulator_tpu.store import annotations as ann
from kube_scheduler_simulator_tpu.store import native_decode
from kube_scheduler_simulator_tpu.store.decode import (
    _native_ctx, decode_chunk_into, decode_pod_result)
from kube_scheduler_simulator_tpu.utils.tracing import TRACER
from test_chunk_decode import _localize_ndarrays

pytestmark = pytest.mark.skipif(get_lib() is None, reason="no native toolchain")

N_NODES, N_PODS = 24, 12
LINEUP = ["NodeResourcesFit", "NodeAffinity", "TaintToleration",
          "PodTopologySpread", "InterPodAffinity"]
FLAVOURS = {
    "plain": {},
    "affinity_tolerations": {"with_affinity": True, "with_tolerations": True},
    "spread_interpod": {"with_spread": True, "with_interpod": True},
}
COUNTERS = {"hits": "node_derived_hits_total",
            "misses": "node_derived_misses_total",
            "evictions": "node_derived_evictions_total"}


def _nodes(n: int = N_NODES) -> list[dict]:
    nodes = make_nodes(n, seed=41, taint_fraction=0.3)
    for node in nodes:
        node["metadata"]["resourceVersion"] = "1"
    return nodes


def _pass(nodes, pods, cfg=None, reuse=None):
    cw = compile_workload(nodes, pods, cfg or PluginSetConfig(enabled=LINEUP),
                          reuse=reuse)
    return cw, replay(cw, chunk=16)


def _native(rr) -> list[dict]:
    out: list = [None] * rr.cw.n_pods
    decode_chunk_into(rr, 0, rr.cw.n_pods, out)
    return out


def _python(rr, monkeypatch) -> list[dict]:
    monkeypatch.setenv("KSS_TPU_DISABLE_NATIVE", "1")
    try:
        return [decode_pod_result(rr, i) for i in range(rr.cw.n_pods)]
    finally:
        monkeypatch.delenv("KSS_TPU_DISABLE_NATIVE")


def _codec_counts() -> dict[str, float]:
    return {what: TRACER.labeled_totals(name, "kind").get("codec_ctx", 0)
            for what, name in COUNTERS.items()}


def _delta(before: dict) -> dict[str, float]:
    return {k: v - before[k] for k, v in _codec_counts().items()}


@pytest.fixture
def codec_calls(monkeypatch):
    """codec_ctx_new / codec_ctx_free as the C library sees them: the
    pointers made and the pointers freed, in order."""
    lib = get_lib()
    made: list = []
    freed: list = []
    new, free = lib.codec_ctx_new, lib.codec_ctx_free

    def counting_new(*args):
        ptr = new(*args)
        made.append(ptr)
        return ptr

    def counting_free(ptr):
        freed.append(ptr)
        free(ptr)

    monkeypatch.setattr(lib, "codec_ctx_new", counting_new)
    monkeypatch.setattr(lib, "codec_ctx_free", counting_free)
    return made, freed


# --- (a) one context for consecutive passes on one table -----------------

@pytest.mark.parametrize("flavour", sorted(FLAVOURS))
def test_consecutive_passes_share_one_context(flavour, monkeypatch, codec_calls):
    made, _ = codec_calls
    nodes = _nodes()
    kw = FLAVOURS[flavour]
    before = _codec_counts()
    cw1, rr1 = _pass(nodes, make_pods(N_PODS, seed=5, **kw))
    first = _native(rr1)
    cw2, rr2 = _pass(nodes, make_pods(N_PODS, seed=6, **kw), reuse=cw1)
    second = _native(rr2)
    assert cw2.node_table is cw1.node_table
    assert _native_ctx(cw1) is _native_ctx(cw2) is not None
    assert _delta(before) == {"hits": 1, "misses": 1, "evictions": 0}
    assert len(made) == 1
    # the carried context's bytes: a freshly built context's (a new table
    # for the same nodes), and the Python encoder's
    cw3, rr3 = _pass(copy.deepcopy(nodes), make_pods(N_PODS, seed=6, **kw))
    assert cw3.node_table is not cw1.node_table
    assert _native_ctx(cw3) is not _native_ctx(cw2)
    assert len(made) == 2
    assert second == _native(rr3) == _python(rr2, monkeypatch)
    assert first == _python(rr1, monkeypatch)
    assert len(first[0]) == 13


def test_the_build_has_a_span_and_later_passes_have_none():
    nodes = _nodes()
    TRACER.reset()
    cw1, rr1 = _pass(nodes, make_pods(N_PODS, seed=5))
    _native(rr1)
    _, rr2 = _pass(nodes, make_pods(N_PODS, seed=6), reuse=cw1)
    _native(rr2)
    spans = TRACER.snapshot()["spans"]
    assert spans["codec_ctx_build"]["count"] == 1
    build = [e for e in TRACER.events(4096) if e["name"] == "codec_ctx_build"]
    assert build[-1]["nodes"] == N_NODES


def test_the_memo_is_looked_up_once_a_cw():
    cw, rr = _pass(_nodes(), make_pods(N_PODS, seed=5))
    before = _codec_counts()
    for _ in range(3):
        _native(rr)
        decode_pod_result(rr, 0)
    assert _delta(before) == {"hits": 0, "misses": 1, "evictions": 0}


# --- (b) nothing of a pass on the shared object --------------------------

@pytest.mark.parametrize("order", ["spread_first", "plain_first"])
def test_passes_with_other_skip_patterns_render_their_own(order, monkeypatch):
    """A pod with a PodTopologySpread constraint runs the plugin's Filter
    and Score; a plain pod Skips both.  The second pass must not render
    through the first pass's plugin-ran rows."""
    nodes = _nodes()
    spread = make_pods(N_PODS, seed=7, with_spread=True)
    plain = make_pods(N_PODS, seed=8)
    queues = [spread, plain] if order == "spread_first" else [plain, spread]
    cw1, rr1 = _pass(nodes, queues[0])
    first = _native(rr1)
    cw2, rr2 = _pass(nodes, queues[1], reuse=cw1)
    second = _native(rr2)
    assert _native_ctx(cw1) is _native_ctx(cw2)
    rows1, rows2 = native_decode.pass_rows(cw1), native_decode.pass_rows(cw2)
    assert (rows1[0] != rows2[0]).any() and (rows1[1] != rows2[1]).any()
    assert first == _python(rr1, monkeypatch)
    assert second == _python(rr2, monkeypatch)
    # and the first pass, read again after the second, is still its own
    assert _native(rr1) == first
    by_pass = {id(spread): first if queues[0] is spread else second,
               id(plain): first if queues[0] is plain else second}
    assert any('"PodTopologySpread"' in a[ann.FILTER_RESULT]
               for a in by_pass[id(spread)])
    assert not any('"PodTopologySpread"' in a[ann.FILTER_RESULT]
                   for a in by_pass[id(plain)])


def test_fused_pod_rung_indexes_the_pass_rows_by_host_index():
    """decode_pod_fused takes its rows at the workload's index hi, which
    is not the compact row on a one-row replay."""
    cw, rr = _pass(_nodes(), make_pods(N_PODS, seed=7, with_spread=True)
                   + make_pods(N_PODS, seed=8))
    ctx = _native_ctx(cw)
    chunked = _native(rr)
    for i in (0, N_PODS - 1, N_PODS, 2 * N_PODS - 1):
        fj, sj, fnj, _ = native_decode.decode_pod_fused(
            ctx, rr, i, i, int(rr.feasible_count[i]) > 1)
        assert fj == chunked[i][ann.FILTER_RESULT]
        assert (sj or "{}") == chunked[i][ann.SCORE_RESULT]
        assert (fnj or "{}") == chunked[i][ann.FINAL_SCORE_RESULT]


# --- (c) a node change: a new context, the old one freed -----------------

def _add(nodes):
    extra = copy.deepcopy(nodes[-1])
    extra["metadata"]["name"] = "node-added"
    extra["metadata"]["labels"]["kubernetes.io/hostname"] = "node-added"
    return nodes + [extra]


def _remove(nodes):
    return nodes[:-1]


def _relabel(nodes):
    nodes = copy.deepcopy(nodes)
    nodes[3]["metadata"]["labels"]["disktype"] = "tape"
    nodes[3]["metadata"]["resourceVersion"] = "2"
    return nodes


@pytest.mark.parametrize("change", [_add, _remove, _relabel],
                         ids=["added", "removed", "relabelled"])
def test_node_change_makes_a_new_context_and_frees_the_old(
        change, monkeypatch, codec_calls):
    made, freed = codec_calls
    nodes = _nodes()
    pods = make_pods(N_PODS, seed=9, with_affinity=True)
    cw1, rr1 = _pass(nodes, pods)
    first = _native(rr1)
    old = _native_ctx(cw1)
    old_ptr, gone = old.ptr, weakref.ref(old)
    # a chunk decode in flight when the table is replaced
    handle = native_decode.decode_chunk_start(old, rr1, 0, N_PODS)
    del old

    before = _codec_counts()
    changed = change(nodes)
    cw2, rr2 = _pass(changed, pods, reuse=cw1)
    assert cw2.node_table is not cw1.node_table
    second = _native(rr2)
    assert _native_ctx(cw2).ptr != old_ptr and len(made) == 2
    # a new table's memo is empty: a miss, and nothing was replaced IN it
    assert _delta(before) == {"hits": 0, "misses": 1, "evictions": 0}
    assert second == _python(rr2, monkeypatch)

    del cw1, rr1
    gc.collect()
    assert gone() is not None and old_ptr not in freed  # the handle holds it
    triples = native_decode.decode_chunk_take(handle)
    assert [t[0] for t in triples] == [a[ann.FILTER_RESULT] for a in first]
    del handle
    gc.collect()
    assert gone() is None
    assert freed == [old_ptr]


# --- (d) the key: another profile is another generation ------------------

class _Refuse(CustomPlugin):
    """Refuses every third node with a message of the caller's."""

    name = "Refuse"

    def __init__(self, message: str):
        self.message = message

    def filter(self, pod, node):
        return self.message if node["metadata"]["name"].endswith(
            ("0", "3", "6")) else None


def _cfg_weights(w):
    return PluginSetConfig(enabled=LINEUP, weights={"NodeAffinity": w})


def _cfg_custom(message):
    return PluginSetConfig(enabled=["NodeResourcesFit", "TaintToleration", "Refuse"],
                           custom={"Refuse": _Refuse(message)})


PROFILE_CHANGES = {
    "weights": (_cfg_weights(2), _cfg_weights(7), ann.FINAL_SCORE_RESULT),
    "scorer_set": (PluginSetConfig(enabled=LINEUP),
                   PluginSetConfig(enabled=LINEUP + ["ImageLocality"]),
                   ann.SCORE_RESULT),
    "custom_messages": (_cfg_custom("refused: quota"),
                        _cfg_custom("refused: maintenance"), ann.FILTER_RESULT),
}


@pytest.mark.parametrize("what", sorted(PROFILE_CHANGES))
def test_another_profile_is_another_generation(what, monkeypatch, codec_calls):
    made, freed = codec_calls
    cfg_a, cfg_b, blob = PROFILE_CHANGES[what]
    nodes = _nodes()
    pods = make_pods(N_PODS, seed=10, with_affinity=True, with_tolerations=True)
    before = _codec_counts()
    cw1, rr1 = _pass(nodes, pods, cfg_a)
    first = _native(rr1)
    cw2, rr2 = _pass(nodes, pods, cfg_b, reuse=cw1)
    assert cw2.node_table is cw1.node_table
    second = _native(rr2)
    assert native_decode.context_key(cw1) != native_decode.context_key(cw2)
    assert _native_ctx(cw2) is not _native_ctx(cw1)
    assert _delta(before) == {"hits": 0, "misses": 2, "evictions": 1}
    assert first == _python(rr1, monkeypatch)
    assert second == _python(rr2, monkeypatch)
    assert [a[blob] for a in first] != [a[blob] for a in second]
    if what == "custom_messages":
        assert "refused: maintenance" in second[0][blob]
        assert "refused: quota" not in second[0][blob]
    # a third pass under the second profile is a hit on its generation
    cw3, rr3 = _pass(nodes, pods, cfg_b, reuse=cw2)
    assert _native_ctx(cw3) is _native_ctx(cw2)
    assert _native(rr3) == second
    # the first profile's context lives while its cw does, then is freed
    first_ptr = _native_ctx(cw1).ptr
    assert first_ptr not in freed
    del cw1, rr1
    gc.collect()
    assert freed == [first_ptr] and len(made) == 2


def test_profiles_that_differ_only_in_scoring_args_share_one_context(monkeypatch):
    """Two schedulerName profiles with one lineup and other pluginConfig
    args (tests/test_multi_profile.py's pair) alternate on one table
    without replacing each other's generation: the codec renders the
    numbers a strategy produced, not the strategy."""
    def cfg(strategy):
        return PluginSetConfig(enabled=LINEUP, args={"NodeResourcesFit": {
            "scoringStrategy": {"type": strategy, "resources": [
                {"name": "cpu", "weight": 1}, {"name": "memory", "weight": 1}]}}})

    nodes = _nodes()
    pods = make_pods(N_PODS, seed=10)
    before = _codec_counts()
    cw1, rr1 = _pass(nodes, pods, cfg("LeastAllocated"))
    cw2, rr2 = _pass(nodes, pods, cfg("MostAllocated"), reuse=cw1)
    first, second = _native(rr1), _native(rr2)
    assert _native_ctx(cw1) is _native_ctx(cw2)
    assert _delta(before) == {"hits": 1, "misses": 1, "evictions": 0}
    assert [a[ann.SCORE_RESULT] for a in first] != \
        [a[ann.SCORE_RESULT] for a in second]
    assert first == _python(rr1, monkeypatch)
    assert second == _python(rr2, monkeypatch)


def test_the_key_names_everything_the_build_reads():
    nodes = _nodes()
    pods = make_pods(N_PODS, seed=10)
    cw, _ = _pass(nodes, pods, _cfg_custom("no"))
    filters, scorers, weights, columns, custom = native_decode.context_key(cw)
    assert filters == tuple(cw.config.filters())
    assert scorers == tuple(cw.config.scorers())
    assert weights == tuple(cw.config.weight(s) for s in scorers)
    assert columns == tuple(cw.schema.columns) and len(columns) == cw.schema.n
    assert custom == (("Refuse", ("no",)),)
    # a resource more in the schema renders in NodeResourcesFit's LUT
    gpu = copy.deepcopy(pods)
    gpu[0]["spec"]["containers"][0]["resources"]["requests"]["example.com/gpu"] = "1"
    cw_gpu, _ = _pass(nodes, gpu, _cfg_custom("no"))
    assert native_decode.context_key(cw_gpu) != native_decode.context_key(cw)


# --- (e) what cannot be a context is carried as None ---------------------

def test_inexpressible_lineup_is_none_and_probed_once_a_table(monkeypatch):
    """NodeResourcesFit's LUT holds 2**(resources + 1) messages: past 16
    bits build_context gives up and the Python encoder serves."""
    nodes = _nodes()
    pods = make_pods(N_PODS, seed=11)
    for k in range(14):
        pods[0]["spec"]["containers"][0]["resources"]["requests"][
            f"example.com/r{k:02d}"] = "1"
    probes: list = []
    probe = native_decode.build_context
    monkeypatch.setattr(native_decode, "build_context",
                        lambda cw: probes.append(cw) or probe(cw))
    before = _codec_counts()
    paths = TRACER.labeled_totals("decode_path_total", "path")
    cw1, rr1 = _pass(nodes, pods)
    assert cw1.schema.n + 1 > native_decode._MAX_FIT_LUT_BITS
    first = _native(rr1)
    cw2, rr2 = _pass(nodes, pods, reuse=cw1)
    second = _native(rr2)
    assert _native_ctx(cw1) is None and _native_ctx(cw2) is None
    assert len(probes) == 1
    assert _delta(before) == {"hits": 1, "misses": 1, "evictions": 0}
    after = TRACER.labeled_totals("decode_path_total", "path")
    assert after.get("python", 0) - paths.get("python", 0) == 2 * N_PODS
    assert after.get("native_chunk", 0) == paths.get("native_chunk", 0)
    assert first == second == _python(rr1, monkeypatch)


def test_disabled_native_never_touches_the_memo(monkeypatch):
    monkeypatch.setenv("KSS_TPU_DISABLE_NATIVE", "1")
    before = _codec_counts()
    cw, rr = _pass(_nodes(), make_pods(N_PODS, seed=5))
    _native(rr)
    assert _native_ctx(cw) is None
    assert _delta(before) == {"hits": 0, "misses": 0, "evictions": 0}
    assert "_native_ctx" not in cw.host


def _raise(cw):
    raise RuntimeError("no LUT for this lineup")


@pytest.mark.parametrize("build", [lambda cw: None, _raise],
                         ids=["returns_none", "raises"])
def test_a_build_that_fails_is_carried_as_none(build, monkeypatch):
    """A missing library (build_context returns None) and a build that
    raises are both carried under the key like an inexpressible lineup:
    probed once a table, and the Python encoder serves."""
    probes: list = []
    monkeypatch.setattr(native_decode, "build_context",
                        lambda cw: probes.append(cw) or build(cw))
    nodes = _nodes()
    pods = make_pods(N_PODS, seed=5)
    before = _codec_counts()
    cw1, rr1 = _pass(nodes, pods)
    first = _native(rr1)
    cw2, rr2 = _pass(nodes, pods, reuse=cw1)
    assert _native_ctx(cw1) is None and _native_ctx(cw2) is None
    assert len(probes) == 1
    assert _delta(before) == {"hits": 1, "misses": 1, "evictions": 0}
    assert len(first[0]) == 13 and _native(rr2) == first
    monkeypatch.undo()
    assert first == _python(rr1, monkeypatch)


def test_missing_library_is_none(monkeypatch):
    monkeypatch.setattr(native_decode, "get_lib", lambda: None)
    cw, rr = _pass(_nodes(), make_pods(N_PODS, seed=5))
    out = _native(rr)
    assert _native_ctx(cw) is None and len(out[0]) == 13


# --- (f) two passes at once on one context --------------------------------

def test_two_threads_decoding_two_passes_at_once_give_the_serial_bytes():
    nodes = _nodes()
    cw1, rr1 = _pass(nodes, make_pods(N_PODS, seed=12, with_spread=True))
    cw2, rr2 = _pass(nodes, make_pods(N_PODS, seed=13, with_affinity=True),
                     reuse=cw1)
    if os.environ.get("KSS_TPU_TSAN_LOCALIZE") == "1":
        # tests/test_native_tsan.py: the codec's inputs off XLA's pages
        _localize_ndarrays(rr1)
        _localize_ndarrays(rr2)
    serial = {id(rr1): _native(rr1), id(rr2): _native(rr2)}
    assert _native_ctx(cw1) is _native_ctx(cw2)
    rounds, errors = 20, []
    start = threading.Barrier(4)

    def reader(rr):
        try:
            start.wait(timeout=30)
            for _ in range(rounds):
                if _native(rr) != serial[id(rr)]:
                    errors.append("bytes differ from the serial run")
                    return
        except Exception as e:  # noqa: BLE001: handed to the assert below
            errors.append(repr(e))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=reader, args=(rr,))
                   for rr in (rr1, rr2, rr1, rr2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
