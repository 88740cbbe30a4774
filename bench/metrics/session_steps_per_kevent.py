"""Calls of the ``sum`` kind's fused program (``jit_fused_step_sum``)
per 1,000 keyed events (bids) in the traced part of the window, in a
session cell.  Session fires at every watermark, the fences around
merges and the hot bidder's repeated panes all end batches early; at
full batches of 256 lanes, one lane a bid, this is 3.9.  The same
reading as ``window_steps_per_kevent``, taken in the session cell."""
from metrics import window_steps_per_kevent


def read(run):
    return window_steps_per_kevent.read(run)
