"""95th percentile over all rounds of the window: delivery of the
round's files to ``step()`` returning with outputs and carry committed."""

import numpy as np


def read(ctx):
    rounds = ctx.get("round_ms") or []
    return float(np.percentile(rounds, 95)) if rounds else None
