"""The one traffic generator: turns a traffic file's ``data`` parameters and
``--seed`` into host arrays.  The program under test receives only these
arrays.  Every seed gives the same shapes and amounts, so the seed changes
values and order, never the work.

Original of the token stream: ``cli.lm.synthetic_tokens`` (the same
arithmetic); original of the image stand-in: ``data/cifar10.py::_synthetic``
(the class-mean-plus-noise idea, here with the sizes a traffic file states).
"""

from __future__ import annotations

import numpy as np


def images(seed: int, *, n_images: int, height: int, width: int,
           channels: int, classes: int, noise: int):
    """CIFAR-shaped uint8 NHWC images with uniform labels: one random mean
    image per class plus uniform noise in ``[-noise, noise]`` — separable,
    so the training loss of a working step falls."""
    rng = np.random.default_rng([int(seed), 0])
    labels = rng.integers(0, classes, size=n_images, dtype=np.int32)
    base = rng.integers(0, 256, size=(classes, height, width, channels),
                        dtype=np.int16)
    jitter = rng.integers(-noise, noise + 1,
                          size=(n_images, height, width, channels),
                          dtype=np.int16)
    jitter += base[labels]
    return np.clip(jitter, 0, 255).astype(np.uint8), labels


def token_blocks(seed: int, *, batch: int, seq_len: int, vocab: int,
                 stream: int = 0):
    """Endless ``(tokens, targets)`` pairs, each ``[batch, seq_len]`` int32,
    targets shifted by one.  ``stream`` separates independent draws from one
    seed (0: the window, 1: the reference check)."""
    rng = np.random.default_rng([int(seed), 1, int(stream)])
    while True:
        block = rng.integers(0, vocab, (batch, seq_len + 1)).astype(np.int32)
        yield block[:, :-1], block[:, 1:]
