"""Peak device memory of the process on its fullest chip, in GiB
(``memory_stats()["peak_bytes_in_use"]`` after the window)."""


def read(context: dict):
    peak = context["memory_peak_bytes"]
    return peak / 2**30 if peak else None
