"""A number the worker took on the host's clock (``key``: a dotted path
into its record), times ``scale``."""

from common import dig


def read(ctx, spec):
    value = dig(ctx["record"], spec["key"])
    return None if value is None else value * spec.get("scale", 1.0)
