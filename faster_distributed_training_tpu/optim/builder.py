"""Optimizer + schedule pairing, mirroring the reference's get_optimizer
selection logic (resnet50_test.py:486-494, transformer_test.py:216-226,
tuning/resnet50_tuning.py:431-440) behind one function."""

from __future__ import annotations

from typing import Optional, Tuple

import optax

from faster_distributed_training_tpu.config import (TrainConfig,
                                                    is_token_model)
from faster_distributed_training_tpu.optim import schedules
from faster_distributed_training_tpu.optim.madgrad import (madgrad,
                                                           mirror_madgrad)
from faster_distributed_training_tpu.optim.ngd import ngd as _ngd


def build_optimizer(cfg: TrainConfig, steps_per_epoch: int,
                    lr_scale: float = 1.0
                    ) -> Tuple[optax.GradientTransformation, optax.Schedule]:
    """Returns (optimizer, schedule).  `lr_scale` is the xN-devices LR
    scaling the reference hard-codes as x4 (resnet50_test.py:482-483) —
    here it is the actual data-parallel world size."""
    base_lr = cfg.lr * lr_scale
    name = cfg.optimizer or ("ngd" if cfg.use_ngd else
                             ("mirror_madgrad" if is_token_model(cfg)
                              else "madgrad"))
    sched_name = cfg.schedule or _default_schedule(name, cfg)

    if sched_name == "multistep":
        schedule = schedules.multistep(base_lr, (10, 20), cfg.gamma,
                                       steps_per_epoch)
    elif sched_name == "cosine":
        schedule = schedules.cosine_annealing(base_lr, 200, steps_per_epoch)
    elif sched_name == "onecycle":
        schedule = schedules.one_cycle(base_lr, cfg.epochs, steps_per_epoch)
    elif sched_name == "step":
        schedule = schedules.step_decay(base_lr, 2, cfg.gamma, steps_per_epoch)
    elif sched_name == "constant":
        schedule = optax.constant_schedule(base_lr)
    else:
        raise ValueError(f"unknown schedule {sched_name!r}")

    if name == "ngd":
        tx = _ngd(schedule, momentum=cfg.momentum,
                      weight_decay=cfg.weight_decay, use_ngd=True,
                      alpha=cfg.ngd_alpha, rank=cfg.ngd_rank,
                      update_period=cfg.ngd_update_period, eta=cfg.ngd_eta,
                      max_dim=cfg.ngd_max_dim)
    elif name == "sgd":
        tx = _ngd(schedule, momentum=cfg.momentum,
                      weight_decay=cfg.weight_decay, use_ngd=False)
    elif name == "madgrad":
        tx = madgrad(schedule, momentum=cfg.momentum,
                              weight_decay=cfg.weight_decay)
    elif name == "mirror_madgrad":
        tx = mirror_madgrad(schedule, momentum=cfg.momentum,
                                     weight_decay=cfg.weight_decay)
    elif name == "adamw":
        tx = optax.adamw(schedule, weight_decay=cfg.weight_decay)
    else:
        raise ValueError(f"unknown optimizer {name!r}")

    if cfg.clip_norm:
        # unscale -> clip_grad_norm_(10) -> step (resnet50_test.py:544-547)
        tx = optax.chain(optax.clip_by_global_norm(cfg.clip_norm), tx)
    return tx, schedule


def _default_schedule(optimizer: str, cfg: TrainConfig) -> str:
    if is_token_model(cfg):
        return "onecycle"                       # transformer_test.py:224
    if cfg.subset_stride > 1 and optimizer == "ngd":
        return "step"                           # tuning/resnet50_tuning.py:435
    if optimizer == "ngd":
        return "multistep"                      # resnet50_test.py:489
    return "cosine"                             # resnet50_test.py:494
