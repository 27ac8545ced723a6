"""Rank-0-gated logging.

The reference prints from every rank (its banner at ``part2/2a/main.py:200-203``
even prints world size/rank per worker).  Under multi-host JAX every process
runs the same program, so the idiomatic surface is: informational prints from
process 0 only, with an escape hatch for per-rank diagnostics.
"""

from __future__ import annotations

import logging
import sys


def _process_index() -> int:
    try:
        import jax

        return jax.process_index()
    except Exception:
        return 0


def rank0_print(*args, all_ranks: bool = False, **kwargs) -> None:
    """print() on process 0 only (or all ranks when all_ranks=True)."""
    if all_ranks or _process_index() == 0:
        print(*args, **kwargs)
        sys.stdout.flush()


def _initialized_process_count() -> int:
    """Process count WITHOUT forcing backend initialization.

    ``jax.process_count()`` initializes (and ``lru_cache``-freezes) the
    XLA backend — called from a log record emitted before
    ``jax.distributed.initialize``, that would both break the later
    init and pin the count at 1 forever.  Multi-host is only knowable
    after distributed init anyway, so ask it: not initialized ⇒ treat
    as single process, touch nothing.
    """
    import jax

    if not jax.distributed.is_initialized():
        return 1  # distributed runtime not up: single-process
    return jax.process_count()  # safe: backend already initialized


class _RankTaggedFormatter(logging.Formatter):
    """Prefixes records with the process index on multi-host runs.

    The decision is PER RECORD, not at logger creation: loggers are
    routinely created at module-import time, before
    ``jax.distributed.initialize`` — an eager ``process_count()`` check
    there reads 1 on every host and the tag would silently never
    activate (the same ordering trap the telemetry sinks solve with a
    lazy rank gate).  Single-process runs stay untagged, and a record
    emitted before distributed init never touches the backend
    (:func:`_initialized_process_count`).
    """

    def format(self, record: logging.LogRecord) -> str:
        base = super().format(record)
        if _initialized_process_count() > 1:
            return f"p{_process_index()} {base}"
        return base


def get_logger(name: str = "dml_tpu") -> logging.Logger:
    logger = logging.getLogger(name)
    if not logger.handlers:
        handler = logging.StreamHandler()
        handler.setFormatter(_RankTaggedFormatter(
            "%(asctime)s %(name)s %(levelname)s %(message)s"
        ))
        logger.addHandler(handler)
        logger.setLevel(logging.INFO)
    # Never propagate to the root logger: an application/basicConfig
    # root handler would print every record a second time.
    logger.propagate = False
    return logger
