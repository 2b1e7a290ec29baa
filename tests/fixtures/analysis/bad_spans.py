"""Seeded observability-conformance violations (AST only): a span
started outside `with` (no guaranteed end on exception paths), a metric
name that fails the Prometheus rules (as a literal, and as one of the
literals a variable can hold), and a reserved label.
"""

from kube_scheduler_simulator_tpu.utils.tracing import TRACER


def unbalanced(work):
    sp = TRACER.span("manual_span")   # unbalanced-span
    sp.__enter__()
    work()
    sp.__exit__(None, None, None)     # not reached if work() raises


def balanced(work):
    with TRACER.span("ok_span"):
        work()


def bad_names():
    TRACER.count("bad-metric.name")            # metric-name
    TRACER.inc("ok_total", **{"__reserved": "x"})   # label-name
    TRACER.observe("ok_seconds", 0.1)


def picked_name(work, by_pod):
    name, stage = "ok_picked_span", "ok_stage"
    if by_pod:
        name = stage = "bad-picked.span"       # metric-name, via the variable
    with TRACER.span(name):
        work(stage)
