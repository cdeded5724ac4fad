"""The one module that knows what a model family is.

A family is a plain record (``Family``) under its ``cfg.model.name`` in
one dict: how to build the module from a ``RunConfig``, what a batch of
it is (``inputs``: ``images`` or ``tokens``), how its part of a program
key is spelled, its model FLOPs, the step metrics it counts itself, its
training objective where that is not the kind's own, what it refuses to
train with and what it says once at start-up. The train
step, the loop, the program registry and the FLOP accounting ask here
and name no family; a new one costs ``models/<family>.py``, its fields
and preset in ``config.py``, and one ``register`` below.

Replaces the reference's per-trainer hardcoded cifar/imagenet dispatch
(reference resnet_model.py:71-74) and the abandoned config-driven
registry sketch (reference models/__init__.py:1-21)."""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax.numpy as jnp

from tpu_resnet.models import (afmoe, lfm2_moe, mlp, qwen3_next, resnet,
                               sdar_moe, smallthinker, transformer)
from tpu_resnet.models.afmoe import Afmoe
from tpu_resnet.models.lfm2_moe import Lfm2Moe
from tpu_resnet.models.mlp import MLP
from tpu_resnet.models.qwen3_next import Qwen3Next
from tpu_resnet.models.sdar_moe import SdarMoe
from tpu_resnet.models.smallthinker import SmallThinker
from tpu_resnet.models.resnet import (
    ResNetV2,
    cifar_resnet_v2,
    imagenet_resnet_v2,
)

__all__ = [
    "Afmoe",
    "Lfm2Moe",
    "MLP",
    "Qwen3Next",
    "ResNetV2",
    "SdarMoe",
    "SmallThinker",
    "cifar_resnet_v2",
    "imagenet_resnet_v2",
    "Family",
    "register",
    "family",
    "family_of",
    "feeds",
    "data_kind",
    "build_model",
    "sample_input",
    "require_image_model",
]

INPUT_KINDS = ("images", "tokens")


@dataclasses.dataclass(frozen=True)
class Family:
    name: str                  # cfg.model.name
    inputs: str                # what a batch is: one of INPUT_KINDS
    module: type               # the class of what build() returns
    build: Callable            # build(cfg) -> module
    # spell(cfg) -> (dataset, name): the data part and the model part of a
    # program key (programs/registry.py::spell joins them with the rest)
    spell: Callable
    # variants(cfg) -> (before, after): suffixes of the family's own
    # switches that change the traced program, around the key's ``_remat``
    variants: Callable = lambda cfg: ("", "")
    # Model FLOPs of one example in a training step, or None: XLA's count
    # of the lowered step is used. Asked again with xla_counted=False
    # where XLA gave none.
    train_flops_per_example: Callable = lambda cfg, xla_counted=True: None
    # step metrics the model counts itself: names in its ``counters``
    # collection, each meaned over the layers that sow it
    counters: Tuple[str, ...] = ()
    # objective(model, rng, inputs, labels) -> (fed, score), or None: the
    # model is fed the batch's inputs and the loss is the kind's own (of
    # tokens: the next id's cross-entropy on ``labels``, ``precision`` the
    # share of positions whose largest logit is the label's). ``rng`` is
    # the step's key, ``fold_in(base_rng, state.step)``; ``fed`` is what
    # the model is applied to; ``score(logits) -> (loss, metrics)``, the
    # metrics holding ``precision`` and whatever else the step reports.
    objective: Optional[Callable] = None
    # refuses(cfg, data_axis) -> what of cfg the family does not train
    # with, in words (train/step.py::check_step_config raises on any)
    refuses: Callable = lambda cfg, data_axis: []
    # startup_events(model, cfg) -> {event: fields}, written once
    startup_events: Callable = lambda model, cfg: {}


_FAMILIES: Dict[str, Family] = {}


def register(fam: Family) -> Family:
    if fam.inputs not in INPUT_KINDS:
        raise ValueError(f"family {fam.name!r}: inputs must be one of "
                         f"{INPUT_KINDS}, got {fam.inputs!r}")
    _FAMILIES[fam.name] = fam
    return fam


def family(cfg) -> Family:
    """The family of ``cfg.model.name``."""
    try:
        return _FAMILIES[cfg.model.name]
    except KeyError:
        raise ValueError(f"unknown model {cfg.model.name!r}; have "
                         f"{sorted(_FAMILIES)}") from None


def family_of(model) -> Family:
    """The family a built module belongs to, by its class."""
    for fam in _FAMILIES.values():
        if isinstance(model, fam.module):
            return fam
    raise ValueError(f"no registered model family builds a "
                     f"{type(model).__name__}; have {sorted(_FAMILIES)}")


def feeds(kind: str) -> str:
    """The families a data set of ``kind`` feeds, for a message."""
    return ", ".join(repr(f.name) for f in sorted(
        _FAMILIES.values(), key=lambda f: f.name) if f.inputs == kind)


def data_kind(cfg) -> str:
    """What a batch of ``cfg.data.dataset`` holds, in the words of
    ``Family.inputs`` (``data/`` owns the kinds and has two)."""
    return "tokens" if cfg.data.dataset == "tokens" else "images"


def build_model(cfg):
    """Build the model from a ``RunConfig`` (tpu_resnet.config.RunConfig)."""
    return family(cfg).build(cfg)


def sample_input(cfg):
    """What a fresh state's weights are drawn on: one image of the data
    set's size or, for a token model, one short sequence of ids (no
    leaf's shape depends on the length, in any token family: a conv
    layer's filter of ``lfm2_moe`` is ``(hidden, taps)``; of ``sdar_moe``
    the eight ids are a noised and a clean copy of one block of four; of
    ``qwen3_next`` the recurrence takes them as one chunk)."""
    if family(cfg).inputs == "tokens":
        return jnp.zeros((1, 8), jnp.int32)
    size = cfg.data.resolved_image_size
    return jnp.zeros((1, size, size, 3), jnp.float32)


def require_image_model(cfg, what: str) -> None:
    """Evaluation, serving and export carry image classifiers only: a
    token model has no evaluation split, no cache for its attention or
    for a conv layer's last positions, and no serving path (ROADMAP
    B-I)."""
    if family(cfg).inputs != "images" or data_kind(cfg) != "images":
        raise NotImplementedError(
            f"{what} is not supported for a token model "
            f"(model.name={cfg.model.name!r}, data.dataset="
            f"{cfg.data.dataset!r}): only `train` carries it; evaluation "
            f"needs a held-out token split and serving a cache for "
            f"attention's keys and values and for a conv layer's last "
            f"positions, and neither exists yet")


register(Family("resnet", "images", ResNetV2, resnet.build, resnet.spell,
                variants=resnet.variants,
                train_flops_per_example=resnet.train_flops_per_example))
register(Family("mlp", "images", MLP, mlp.build, mlp.spell))
register(Family("afmoe", "tokens", Afmoe, afmoe.build, afmoe.spell,
                train_flops_per_example=afmoe.train_flops_per_example,
                counters=afmoe.COUNTERS, refuses=afmoe.refuses,
                startup_events=afmoe.startup_events))
register(Family("sdar_moe", "tokens", SdarMoe, sdar_moe.build,
                sdar_moe.spell,
                train_flops_per_example=sdar_moe.train_flops_per_example,
                counters=sdar_moe.COUNTERS, objective=sdar_moe.objective,
                refuses=sdar_moe.refuses,
                startup_events=sdar_moe.startup_events))
register(Family("lfm2_moe", "tokens", Lfm2Moe, lfm2_moe.build,
                lfm2_moe.spell,
                train_flops_per_example=lfm2_moe.train_flops_per_example,
                counters=lfm2_moe.COUNTERS, refuses=transformer.refuses,
                startup_events=lfm2_moe.startup_events))
register(Family("qwen3_next", "tokens", Qwen3Next, qwen3_next.build,
                qwen3_next.spell,
                train_flops_per_example=qwen3_next.train_flops_per_example,
                counters=qwen3_next.COUNTERS, refuses=qwen3_next.refuses,
                startup_events=qwen3_next.startup_events))
register(Family("smallthinker", "tokens", SmallThinker, smallthinker.build,
                smallthinker.spell,
                train_flops_per_example=smallthinker.train_flops_per_example,
                counters=smallthinker.COUNTERS, refuses=transformer.refuses,
                startup_events=smallthinker.startup_events))
