"""Device time of one ``fused_step`` program: the ``XLA Modules``
events of ``jit_fused_step`` in the trace, their summed duration over
their count, in microseconds."""

def _is_step(name: str) -> bool:
    return "fused_step" in name


def read(run):
    tr = run.get("trace")
    if tr is None:
        return None
    n, s = tr.module_time(_is_step)
    return s / n * 1e6 if n else None
