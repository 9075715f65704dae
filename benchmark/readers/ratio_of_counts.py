"""``numerator`` over ``denominator``, two counts of the record (dotted
paths), times ``scale``."""

from common import dig


def read(ctx, spec):
    num = dig(ctx["record"], spec["numerator"])
    den = dig(ctx["record"], spec["denominator"])
    if num is None or not den:
        return None
    return num / den * spec.get("scale", 1.0)
