"""Calls of the fused plane's batched directory clear (``drop_slots``)
per 1,000 keyed events the stateful operator took up, over the traced
run's whole window.  Sessions close one by one at every watermark and
their panes purge one lateness later, where fixed windows purge a
window's panes at once: this reads whether the purges still batch (at
most one call per device step, 32 slots a call).  Silent where the run
called no device program."""


def read(run):
    calls = run.get("calls") or {}
    if not calls or run.get("events", 0) <= 0:
        return None
    return calls.get("drop_slots", 0) / (run["events"] / 1e3)
