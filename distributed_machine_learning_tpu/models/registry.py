"""Model registry: name → constructor, shared by the CLIs.

The reference exposes exactly one model factory (`VGG11()` at
`part1/model.py:49-50`); its cfg table lists VGG11/13/16/19
(`part1/model.py:3-8`) and BASELINE.json's configs name ResNet-18 (with
ResNet-50 as the scale-out stretch).  All of those are registered here.

`use_bn` semantics: VGG takes it literally (off = part1/2a/2b parity, on
= part3 parity — `part3/model.py:24`); ResNets are BN-architectures, so
they accept and ignore it (BN always on).
"""

from __future__ import annotations

from typing import Any

from distributed_machine_learning_tpu.models import resnet, vgg

# Derived from each family's cfg table — one source of truth; a variant
# added to a model module's _cfg is immediately available here.
_VGG_NAMES = {k.lower(): k for k in vgg._cfg}
_RESNET_NAMES = {k.lower(): k for k in resnet._cfg}


def list_models() -> list[str]:
    return sorted(_VGG_NAMES) + sorted(_RESNET_NAMES)


def get_model(name: str, *, use_bn: bool = False, compute_dtype: Any = None,
              num_classes: int = 10, cifar_stem: bool = True):
    """Build a model by lowercase name (e.g. "vgg11", "resnet18")."""
    key = name.lower()
    kw: dict[str, Any] = {"num_classes": num_classes}
    if compute_dtype is not None:
        kw["compute_dtype"] = compute_dtype
    if key in _VGG_NAMES:
        return vgg.VGG(name_cfg=_VGG_NAMES[key], use_bn=use_bn, **kw)
    if key in _RESNET_NAMES:
        return resnet.ResNet(name_cfg=_RESNET_NAMES[key],
                             cifar_stem=cifar_stem, **kw)
    raise ValueError(f"unknown model {name!r}; available: {list_models()}")
