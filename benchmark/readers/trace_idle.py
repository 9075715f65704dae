"""The share of the traced window, first device operation to last, in
which no operation ran on the chip, in percent, averaged over the traced
chips."""

import stats
import tracecalc
from common import traced_devices


def read(ctx, spec):
    shares = []
    for dev, _, _ in traced_devices(ctx):
        lo, hi = tracecalc.window(dev)
        shares.append(1.0 - stats.total(tracecalc.busy(dev)) / (hi - lo))
    return sum(shares) / len(shares) * 100.0 if shares else None
