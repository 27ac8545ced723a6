"""part1 — single-device baseline (reference ``part1/main.py``).

No flags in the reference (``part1/main.py:129-130``); batch 256
(``part1/main.py:18``), VGG-11 without BatchNorm, plain jitted train step,
no collectives.  Run: ``python -m distributed_machine_learning_tpu.cli.part1``.
"""

from __future__ import annotations

from distributed_machine_learning_tpu.cli.common import (
    RunResult,
    make_flag_parser,
    parse_flags,
    run_part,
)

BATCH_SIZE = 256  # part1/main.py:18


def main(argv=None) -> RunResult:
    args = parse_flags(make_flag_parser(__doc__), argv)
    return run_part("none", per_rank_batch=BATCH_SIZE, use_bn=False, args=args)


if __name__ == "__main__":
    main()
