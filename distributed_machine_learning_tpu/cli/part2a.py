"""part2a — centralized gather/scatter sync (reference ``part2/2a/main.py``).

The reference gathers every gradient to rank 0, sums, scatters back
(``part2/2a/main.py:89-116``; SUM semantics, batch 64/worker).  Here the
strategy is ``gather_scatter``: all-gather + rank-order sum on every
device (SURVEY.md §7.3).  Flags kept verbatim from
``part2/2a/main.py:210-218``.
"""

from __future__ import annotations

from distributed_machine_learning_tpu.cli.common import (
    RunResult,
    make_flag_parser,
    parse_flags,
    run_part,
)

BATCH_SIZE = 64  # per worker — part2/2a/main.py:33


def main(argv=None) -> RunResult:
    args = parse_flags(make_flag_parser(__doc__), argv)
    return run_part("gather_scatter", per_rank_batch=BATCH_SIZE, use_bn=False, args=args)


if __name__ == "__main__":
    main()
