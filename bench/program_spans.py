"""Seconds of the program's own spans (`repro.obs.span`) in the traced
window.  While a profiler session records, each span of the program adds
its seconds to the registry histogram `obs_span_seconds{span=<name>}`,
and a run's one profiler session is its traced window.  A program
without that histogram gives None, as does an untraced run."""


def per_update(run, name: str):
    """Seconds of the span `name` in the traced window, per update."""
    n = run.work.get("updates")
    if run.trace is None or not n:
        return None
    from repro.obs import REGISTRY

    h = REGISTRY.get("obs_span_seconds", span=name)
    return h.sum / n if h is not None and h.count else None
