"""distributed_machine_learning_tpu — a TPU-native distributed-training framework.

A brand-new JAX/XLA/pjit/Pallas framework with the capabilities of the
reference ``Rishideep08/Distributed-Machine-Learning`` (a three-part
torch.distributed/gloo CIFAR-10 training assignment — see SURVEY.md):

- ``models/``    Flax model zoo: cfg-driven VGG-11/13/16/19 (reference
                 ``part1/model.py:3-8``) with optional BatchNorm, plus
                 ResNet-18/50 (BASELINE.json configs).
- ``data/``      CIFAR-10 pipeline without torchvision: pickle-batch parser,
                 device-side RandomCrop(32, pad=4)+flip augmentation, and
                 ``DistributedSampler(shuffle=False)``-compatible sharding
                 (reference ``part2/2a/main.py:158-167``).
- ``parallel/``  the pluggable gradient-sync layer — the reference's only
                 varying layer (SURVEY.md §1): ``none`` (part1),
                 ``gather_scatter`` (part2a), ``all_reduce`` (part2b),
                 ``ring`` (part3 north-star: bucketed lax.ppermute ring).
- ``ops/``       the collective building blocks: psum/pmean wrappers,
                 all-gather-based centralized sum, and the hand-rolled
                 bucketed ring reduce-scatter/all-gather on ``lax.ppermute``.
- ``train/``     jitted train/eval steps over a ``jax.sharding.Mesh`` via
                 ``shard_map``; SGD with torch-update semantics; the
                 40-iteration timing driver (reference ``part1/main.py:32-58``).
- ``runtime/``   multi-host bootstrap (``--master-ip/--rank/--num-nodes`` →
                 ``jax.distributed.initialize``), mesh construction, seeding.
- ``cli/``       the four entrypoints with the reference's flags kept verbatim.
- ``utils/``     timing harness, rank-0-gated logging, checkpointing.

Unlike the reference — four copy-pasted clones varying only in the sync
layer (SURVEY.md §1) — this is one shared core with the sync strategy as a
plug-in.
"""

import time as _time

#: ``perf_counter`` at the package's first import, read before any other
#: import of it: the zero of the start-up record (``telemetry/startup.py``).
IMPORT_STARTED = _time.perf_counter()

__version__ = "0.1.0"

from distributed_machine_learning_tpu import utils  # noqa: F401
