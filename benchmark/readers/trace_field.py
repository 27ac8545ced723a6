"""One number of the device-trace reduction (``trace_reduce.reduce``).
``needs`` names a flag of the reduction that must be true for the metric to
exist at all (collective time on a program with no collective is no
reading, not zero)."""


def read(context: dict, field: str, needs: str | None = None):
    reduced = context["trace"]
    if reduced is None or (needs is not None and not reduced.get(needs)):
        return None
    return reduced.get(field)
