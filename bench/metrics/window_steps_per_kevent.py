"""Calls of the ``sum`` kind's fused program (``jit_fused_step_sum``)
in the traced part of the window, per 1,000 keyed events the stateful
operator took up in that part (the window's events less those after the
profiler stopped).  At full batches of 256 lanes, one lane a keyed
event, this is 3.9.  Silent where no program carries its kind in its
name."""


def _is_sum_step(name: str) -> bool:
    return "fused_step_sum" in name


def read(run):
    tr, host = run.get("trace"), run.get("host")
    if tr is None or not host:
        return None
    events = run["events"] - host["events"]
    n, _ = tr.module_time(_is_sum_step)
    if not n or events <= 0:
        return None
    return n / (events / 1e3)
