"""Device time of one window-count step: the ``XLA Modules`` events of
the ``sum`` kind's fused program (``jit_fused_step_sum``) in the trace,
their summed duration over their count, in microseconds.  Silent where
no program carries its kind in its name."""


def _is_sum_step(name: str) -> bool:
    return "fused_step_sum" in name


def read(run):
    tr = run.get("trace")
    if tr is None:
        return None
    n, s = tr.module_time(_is_sum_step)
    return s / n * 1e6 if n else None
