"""completion_mean_s: mean of due time to last token (s).

Over every request due in the window; an unfinished request counts with
the time until the run ended, a lower bound.
"""

import numpy as np

from _lib import done_or_stop


def read(ctx):
    """Mean over the window's requests of finish - due time."""
    if not ctx.window:
        return None
    return float(np.mean([done_or_stop(r.finish, ctx) - r.due
                          for r in ctx.window]))
