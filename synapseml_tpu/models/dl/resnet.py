"""ResNet backbones in flax for the vision classifier.

Replaces the reference's torchvision backbones under Horovod
(reference: deep-learning/.../dl/LitDeepVisionModel.py:1-233 — backbone by
name from torchvision, loss/optimizer by name).  NHWC layout (TPU-native
conv layout), bfloat16 activations, BatchNorm with running stats carried in
a separate ``batch_stats`` collection.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp

ModuleDef = Any


class ResNetBlock(nn.Module):
    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides)(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters, (1, 1), self.strides,
                                 name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class BottleneckResNetBlock(nn.Module):
    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1), self.strides,
                                 name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    act: Callable = nn.relu
    #: rematerialize each residual block in the backward pass
    #: ("none" | "dots_saveable" | "full"/"blocks", see
    #: models/dl/precision.py:remat_policy): where the fine-tune step
    #: is bandwidth-bound, trading HBM round trips of saved activations
    #: for recompute FLOPs is the byte-diet lever.
    #: Bit-exact vs "none" by construction — the recomputation re-runs
    #: the identical ops (pinned in tests/test_perf_roofline.py).
    remat: str = "none"

    @nn.compact
    def __call__(self, x, train: bool = False):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, epsilon=1e-5, dtype=self.dtype)
        x = conv(self.num_filters, (7, 7), (2, 2), padding=[(3, 3), (3, 3)],
                 name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = self.act(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        from .precision import remat_policy
        use_remat, policy = remat_policy(self.remat)
        block_cls = self.block_cls
        if use_remat:
            # (x) is the only traced arg; the train flag is baked into
            # the bound norm partial, so no static_argnums needed
            block_cls = nn.remat(self.block_cls, policy=policy)
        # explicit names matching the unwrapped auto-naming
        # ("<BlockCls>_<k>"): the remat wrapper must not change param
        # paths, or checkpoints/pretrained imports written without remat
        # would not load (and init would draw DIFFERENT weights — remat
        # is pinned bit-exact vs 'none')
        base_name = self.block_cls.__name__
        k = 0
        for i, block_size in enumerate(self.stage_sizes):
            for j in range(block_size):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = block_cls(self.num_filters * 2 ** i,
                              conv=conv, norm=norm, act=self.act,
                              strides=strides, name=f"{base_name}_{k}")(x)
                k += 1
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(x)
        return x


BACKBONES = {
    "resnet18": partial(ResNet, stage_sizes=[2, 2, 2, 2], block_cls=ResNetBlock),
    "resnet34": partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=ResNetBlock),
    "resnet50": partial(ResNet, stage_sizes=[3, 4, 6, 3],
                        block_cls=BottleneckResNetBlock),
    "resnet101": partial(ResNet, stage_sizes=[3, 4, 23, 3],
                         block_cls=BottleneckResNetBlock),
    "resnet152": partial(ResNet, stage_sizes=[3, 8, 36, 3],
                         block_cls=BottleneckResNetBlock),
}


def make_backbone(name: str, num_classes: int, **kw) -> nn.Module:
    if name not in BACKBONES:
        raise ValueError(f"unknown backbone {name!r}; have {sorted(BACKBONES)}")
    return BACKBONES[name](num_classes=num_classes, **kw)
