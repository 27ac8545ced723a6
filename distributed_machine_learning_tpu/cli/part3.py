"""part3 — bucketed ring all-reduce (reference ``part3/main.py``).

The reference wraps the model in DDP with 25 MB buckets
(``part3/main.py:137``) — bucketed ring all-reduce with averaging, BN
enabled (``part3/model.py:24``).  Here: the hand-rolled explicit
``lax.ppermute`` ring (the north-star), 25 MB buckets, mean semantics,
VGG-11 with BatchNorm.

Gradient wire compression (``--ring-compress {none,bf16,int8,topk}``,
``--ring-topk-frac``): compress each ring hop's payload — int8 with
per-chunk fp32 scales or magnitude top-k sparsification, both carrying
an error-feedback residual across steps (EF-SGD), or a cast-only bf16
wire.  ~4x fewer bytes on the wire for int8/topk (read off the
compiled program: ``tests/test_overlap_audit.py``); ``--wire-dtype bfloat16``
is the deprecated spelling of ``--ring-compress bf16``.
"""

from __future__ import annotations

from distributed_machine_learning_tpu.cli.common import (
    RunResult,
    make_flag_parser,
    parse_flags,
    run_part,
)
from distributed_machine_learning_tpu.ops.ring import DEFAULT_BUCKET_BYTES

BATCH_SIZE = 64  # per worker — part3/main.py:31


def main(argv=None) -> RunResult:
    parser = make_flag_parser(__doc__)
    parser.add_argument("--bucket-mb", default=25, type=int,
                        help="ring all-reduce bucket size (part3/main.py:137)")
    args = parse_flags(parser, argv)
    return run_part(
        "ring",
        per_rank_batch=BATCH_SIZE,
        use_bn=True,
        args=args,
        strategy_kwargs={"bucket_bytes": args.bucket_mb * 2**20},
    )


if __name__ == "__main__":
    main()
