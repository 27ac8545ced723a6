"""part2b — collective all-reduce sync (reference ``part2/2b/main.py``).

One ``dist.all_reduce(SUM)`` per parameter (``part2/2b/main.py:101-106``)
becomes one ``lax.psum`` per gradient leaf; SUM semantics (no division by
world size — SURVEY.md §2.4), batch 64/worker.
"""

from __future__ import annotations

from distributed_machine_learning_tpu.cli.common import (
    RunResult,
    make_flag_parser,
    parse_flags,
    run_part,
)

BATCH_SIZE = 64  # per worker — part2/2b/main.py:31


def main(argv=None) -> RunResult:
    args = parse_flags(make_flag_parser(__doc__), argv)
    return run_part("all_reduce", per_rank_batch=BATCH_SIZE, use_bn=False, args=args)


if __name__ == "__main__":
    main()
