"""A percentile of the gaps between successive step completions of the
untraced window (milliseconds, host clock on completion barriers)."""

import stats


def read(ctx, spec):
    return stats.percentile(ctx["gaps_ms"], spec["percentile"])
