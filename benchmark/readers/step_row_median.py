"""Median of one field of ``train_epoch``'s telemetry step rows
(``data_wait_s``, ``place_s``, ``dispatch_s``, ``block_s``) over the window's
steps outside the traced stretch, times ``scale``."""

import statistics


def read(context: dict, field: str, scale: float = 1.0):
    values = [row[field] for row in context["step_rows"] if field in row]
    if not values:
        return None
    return scale * statistics.median(values)
