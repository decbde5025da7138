"""Reader ``fact``: a host counter or a list of host-clock samples the
driver kept (``key``), reduced by ``stat`` (``p50``, ``p95``, ``mean``,
``max``, ...) and multiplied by ``scale``."""

from .. import stats


def read(ctx, facts, args):
    value = facts.get(args["key"])
    if isinstance(value, list):
        value = stats.stat(value, args.get("stat", "mean"))
    if value is None:
        return None
    return float(value) * float(args.get("scale", 1.0))
