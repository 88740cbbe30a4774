"""Host time of the engine (``Engine``/``Sim``, ``StatefulOp``,
``WindowedStatefulOp``): wall time of the window outside the fused
plane's device-call spans, per 1,000 keyed events the stateful operator
took up.  Taken over the part of the traced run's window after the
profiler stopped."""


def read(run):
    h = run.get("host")
    if not h or h["events"] <= 0:
        return None
    return (h["wall_s"] - h["inside_s"]) * 1e3 / (h["events"] / 1e3)
