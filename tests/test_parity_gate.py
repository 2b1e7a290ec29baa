"""The streamed parity gate (reference_impl/parity_gate.py): the check
itself and the oracle-child failure handling.  chip_smoke.py's gate
phase and tools/parity_fullscale.py take their verdict from it
(PARITY.md "The parity protocol"), so a dead oracle child must never
read as a parity failure, nor a mismatch as a pass."""

from __future__ import annotations

from kube_scheduler_simulator_tpu.reference_impl import parity_gate


def test_stream_oracle_parity_ok_and_digest():
    r = parity_gate.stream_oracle_parity(1, 0.02, 0, want_digest=True)
    assert r["ok"] is True
    assert r["compared"] == r["pods"] > 0
    assert r["keys_checked"] == 13 * r["pods"]
    assert r["mismatches"] == 0 and r["first_mismatch"] is None
    assert len(r["sha256"]) == 64
    assert r["oracle_rc"] == 0


def test_stream_oracle_parity_heartbeat_fires():
    beats = []
    r = parity_gate.stream_oracle_parity(1, 0.02, 0, heartbeat=beats.append)
    assert r["ok"] and len(beats) >= r["pods"]


def test_oracle_child_death_is_not_a_parity_failure(monkeypatch):
    # a dying child (the round-4 OOM shape) must be reported as an
    # environment failure, not as mismatches
    monkeypatch.setattr(
        parity_gate, "_ORACLE_CHILD",
        "import sys\nsys.exit(137)\n" + "# {repo} {idx} {scale} {seed}\n")
    r = parity_gate.stream_oracle_parity(1, 0.02, 0)
    assert r["ok"] is False
    assert r.get("oracle_died") is True
    assert r["mismatches"] == 0
    assert r["oracle_rc"] == 137


def test_run_parity_gate_retries_smaller_on_child_death(monkeypatch):
    calls = []
    real = parity_gate.stream_oracle_parity

    def fake(idx, scale, seed, chunk=64, want_digest=False, heartbeat=None):
        calls.append(scale)
        if len(calls) == 1:
            return {"ok": False, "pods": 10, "compared": 3,
                    "keys_checked": 39, "mismatches": 0,
                    "first_mismatch": None, "sha256": None,
                    "oracle_rc": -9, "oracle_err": "Killed",
                    "oracle_died": True, "replay_seconds": 0,
                    "oracle_seconds": 0}
        return real(idx, scale, seed, chunk=chunk, heartbeat=heartbeat)

    monkeypatch.setattr(parity_gate, "stream_oracle_parity", fake)
    assert parity_gate.run_parity_gate(1, 0.08, 0) is True
    assert calls == [0.08, 0.02]  # retried once at a quarter of the scale


def test_run_parity_gate_mismatch_fails(monkeypatch):
    def fake(idx, scale, seed, chunk=64, want_digest=False, heartbeat=None):
        return {"ok": False, "pods": 10, "compared": 10, "keys_checked": 130,
                "mismatches": 1, "sha256": None, "oracle_rc": 0,
                "oracle_err": "", "replay_seconds": 0, "oracle_seconds": 0,
                "first_mismatch": {"pod": 3, "key": "k", "dev": "a",
                                   "oracle": "b"}}

    monkeypatch.setattr(parity_gate, "stream_oracle_parity", fake)
    assert parity_gate.run_parity_gate(1, 0.08, 0) is False
