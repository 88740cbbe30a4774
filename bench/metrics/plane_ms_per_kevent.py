"""Host time of the fused plane (``FusedPlane`` staging and its
synchronous device calls: dispatch, wait, device-to-host reads): wall
time inside the outermost ``bench.batch_step`` and ``tac_jax`` call
spans, per 1,000 keyed events the stateful operator took up.  Taken
over the part of the traced run's window after the profiler stopped."""


def read(run):
    h = run.get("host")
    if not h or h["events"] <= 0:
        return None
    return h["inside_s"] * 1e3 / (h["events"] / 1e3)
