"""The program's own start-up record
(``distributed_machine_learning_tpu/telemetry/startup.py``), read in-process
after the window: what set-up was made of, from the inside.

``kind`` ``"span"``: the seconds of the closed ``startup.*`` spans named,
summed (``startup`` itself: package import to the first loss).  ``kind``
``"counter"``: the ``jax_*_total`` counters named, less those in ``less``,
over all phases or those in ``phase``, AS THEY STOOD WHEN THE NEWEST
``train_epoch`` BEGAN — the window's: the CLI's programs and the reference
check's, neither the window nor the lowering the harness does after it.  A
record without such a span, and one no ``train_epoch`` has snapshotted, give
None: the metric is left out."""

from distributed_machine_learning_tpu.telemetry import startup


def read(context: dict, kind: str, name: list, less: tuple = (),
         phase: tuple = (None,)):
    record = startup.record()
    if kind == "span":
        return record.seconds(name)
    if kind != "counter":
        raise ValueError(f"kind must be 'span' or 'counter', got {kind!r}")
    if record.at_epoch is None:
        return None
    totals = [record.totals(p, at_epoch=True) for p in phase]
    return sum(t.get(n, 0) for t in totals for n in name) \
        - sum(t.get(n, 0) for t in totals for n in less)
