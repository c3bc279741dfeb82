"""Federated tasks: the model-side contract consumed by the engine.

A :class:`repro.fed.tasks.base.FedTask` bundles everything the federated
stack needs to know about *what is being trained* — parameter init, the
per-sample-weighted loss the sum-combine algorithms differentiate, the
local objective FedAvg descends, the task's metric schema and jitted
eval probe, and a synthetic data source — so that
:mod:`repro.fed.engine` / :mod:`repro.fed.runtime` stay model-agnostic.

Built-in tasks:

* :class:`repro.fed.tasks.mlp.MLPTask` — the paper's Section-V MNIST MLP
  (the default task of every :mod:`repro.fed.runtime` wrapper).
* :func:`repro.fed.tasks.transformer.transformer_task` — a reduced
  decoder-only LM from the model zoo trained as a federated next-token
  task; :func:`~repro.fed.tasks.transformer.chip_share_task` — one at
  its published widths, cut only to one chip's layers and vocabulary.
* :func:`repro.fed.tasks.rwkv6.rwkv6_task` — the attention-free RWKV-6
  family through the same LM task machinery.

``transformer`` / ``rwkv6`` are imported lazily (PEP 562) so that the
MLP-only paths never pay the model-zoo import.
"""
from repro.fed.tasks import base, mlp  # noqa: F401
from repro.fed.tasks.base import (  # noqa: F401
    FedTask, LocalObjective, SumLoss, TaskData)
from repro.fed.tasks.mlp import MLPTask  # noqa: F401

__all__ = [
    "base", "mlp", "FedTask", "LocalObjective", "SumLoss", "TaskData",
    "MLPTask", "LMTask", "transformer_task", "chip_share_task",
    "rwkv6_task",
]


def __getattr__(name):
    if name in ("LMTask", "transformer_task", "chip_share_task"):
        from repro.fed.tasks import transformer
        return getattr(transformer, name)
    if name == "rwkv6_task":
        from repro.fed.tasks import rwkv6
        return rwkv6.rwkv6_task
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
