"""ImageLocality score kernel.

Upstream v1.32 `imagelocality`: Score only (no Filter, no NormalizeScore),
recorded by the reference shim like every score plugin (reference:
simulator/scheduler/plugin/wrappedplugin.go:420-445).

    sumScores = Σ over the pod's (init)containers whose image exists on
                the node of  size_bytes * (nodes_having_image / total_nodes)
    score     = 100 * (clamp(sumScores, min, max) - min) / (max - min)
    min       = 23 MB * numContainers,  max = 1000 MB * numContainers

Node images never change during a replay (KWOK-style nodes have no
kubelet pulling images), so the whole score precompiles to a static
[P, N] tensor — the kernel is a row gather.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

NAME = "ImageLocality"

MB = 1024 * 1024
MIN_THRESHOLD = 23 * MB
MAX_CONTAINER_THRESHOLD = 1000 * MB
MAX_NODE_SCORE = 100


class ImageXS(NamedTuple):
    score: jnp.ndarray  # [P, N] int64, precomputed


def normalized_image_name(name: str) -> str:
    """upstream normalizedImageName: append :latest when untagged."""
    if name.rfind(":") <= name.rfind("/") and "@" not in name:
        name += ":latest"
    return name


def node_image_states(nodes: list[dict]) -> dict[str, tuple[int, set[int]]]:
    """image name -> (size_bytes, node indices having it)."""
    states: dict[str, tuple[int, set[int]]] = {}
    for j, node in enumerate(nodes):
        for img in ((node.get("status") or {}).get("images")) or []:
            size = int(img.get("sizeBytes") or 0)
            for nm in img.get("names") or []:
                nm = normalized_image_name(nm)
                # first-seen size wins, like nodeinfo's imageStates
                _, have = states.setdefault(nm, (size, set()))
                have.add(j)
    return states


def pod_images(pod: dict) -> tuple[list[str], int]:
    """(normalized image names, container count incl. init containers)."""
    spec = pod.get("spec") or {}
    containers = (spec.get("initContainers") or []) + (spec.get("containers") or [])
    return [
        normalized_image_name(c.get("image") or "") for c in containers if c.get("image")
    ], len(containers)


def calculate_priority(sum_scores: int, num_containers: int) -> int:
    max_threshold = MAX_CONTAINER_THRESHOLD * num_containers
    if sum_scores < MIN_THRESHOLD:
        sum_scores = MIN_THRESHOLD
    elif sum_scores > max_threshold:
        sum_scores = max_threshold
    return MAX_NODE_SCORE * (sum_scores - MIN_THRESHOLD) // (max_threshold - MIN_THRESHOLD)


def score_for(pod: dict, states, n_nodes: int) -> np.ndarray:
    """[N] int64 ImageLocality score, the scalar/parity formula."""
    return _score_row(*pod_images(pod), states, n_nodes)


def _score_row(images: list[str], num_containers: int, states,
               n_nodes: int) -> np.ndarray:
    out = np.zeros(n_nodes, dtype=np.int64)
    if not images or num_containers == 0:
        return out
    sums = np.zeros(n_nodes, dtype=np.int64)
    for nm in images:
        st = states.get(nm)
        if st is None:
            continue
        size, have = st
        scaled = int(float(size) * (float(len(have)) / float(n_nodes)))
        for j in have:
            sums[j] += scaled
    for j in range(n_nodes):
        out[j] = calculate_priority(int(sums[j]), num_containers)
    return out


def build(table, nodes: list[dict], pods: list[dict],
          host_out: dict | None = None) -> ImageXS:
    """table: the NodeTable built from `nodes`; the image states and the
    score row per (image names, container count) are kept on it (the
    table's identity covers status.images: node_key holds every node's
    resourceVersion)."""
    derived = table.derived
    n = len(nodes)

    def states():
        return derived.once("image_states", lambda: node_image_states(nodes))

    score = np.zeros((len(pods), n), dtype=np.int64)
    for i, pod in enumerate(pods):
        images, num_containers = pod_images(pod)
        score[i] = derived.row(
            "image_row", (tuple(images), num_containers),
            lambda: _score_row(images, num_containers, states(), n))
    if host_out is not None:
        # score_kernel is a pure pass-through of this precompiled row: the
        # compact replay keeps it host-resident ("host" group, no D2H)
        host_out.setdefault("static_score_rows", {})[NAME] = score
    return ImageXS(score=score)


def score_kernel(sl: ImageXS) -> jnp.ndarray:
    return sl.score.astype(jnp.int64)
