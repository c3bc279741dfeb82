"""Language-model architectures as federated tasks.

:class:`LMTask` wraps any :class:`repro.configs.base.ModelConfig` family
the model zoo can build (dense GQA decoders, MoE, RWKV-6, Griffin
hybrids, …) as a next-token-prediction :class:`~repro.fed.tasks.base.FedTask`:
each client holds token sequences, uploads the per-sample-weighted
gradient of the sequence-mean cross-entropy (Algorithm 1's q0 — or its
locally-trained model under FedAvg), and the server runs the same SSCA
recursions as for the paper's MLP.  This is the paper's "model
specification is free" claim made executable: the transformer trains
through the *full* federated stack — client mesh, secure aggregation,
upload compression — not just the single-process ``launch/steps`` path.

``batch`` layout: ``x`` and ``y`` both carry the (B, S) int32 token
matrix (the loss shifts internally; keeping the engine's uniform
(x, y[, w]) triple means zero engine special-casing).  MoE auxiliary
losses are dropped from the federated objective (the reduced federated
configs are aux-free families; wire the aux in before adding a
federated MoE task).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.base import ModelConfig, reduced
from repro.data import synthetic
from repro.fed.tasks.base import TaskData
from repro.models import build_model


@dataclasses.dataclass(frozen=True)
class LMTask:
    """Next-token prediction over a model-zoo config.

    ``cfg`` must be hashable (:class:`ModelConfig` is a frozen
    dataclass), so equal tasks — and therefore the algorithm instances
    holding their bound loss methods — share the engine's compiled
    chunk and eval probe across runs.
    """
    cfg: ModelConfig
    seq_len: int = 32

    metric_names = ("train_cost", "test_accuracy")

    @property
    def name(self) -> str:
        return self.cfg.name

    def _model(self):
        return build_model(self.cfg)

    def init_params(self, key):
        return self._model().init(key)

    @property
    def tokens_per_sample(self) -> int:
        """Tokens in one training sample (a sequence): the engine counts
        the tokens a round trains on with it."""
        return self.seq_len

    def _per_example_ce(self, params, tokens) -> jnp.ndarray:
        """Per-sequence mean next-token cross-entropy, (B,) float32."""
        logits = self._model().forward(params, {"tokens": tokens})
        with jax.named_scope("loss"):
            logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32),
                                      axis=-1)
            tgt = tokens[:, 1:]
            nll = -jnp.take_along_axis(logp, tgt[..., None],
                                       axis=-1)[..., 0]
            return jnp.mean(nll, axis=-1)

    def loss_sum(self, params, batch) -> jnp.ndarray:
        """Σ_n w_n ℓ_n with ℓ_n the sequence-mean CE — additive in the
        batch, so the super-batch shortcut and the per-client secure
        upload are both exact."""
        x, _, w = batch
        return jnp.sum(w * self._per_example_ce(params, x))

    def mean_loss(self, params, batch) -> jnp.ndarray:
        x, _ = batch
        return jnp.mean(self._per_example_ce(params, x))

    def measure(self, params, x_tr, y_tr, x_te, y_te):
        logits = self._model().forward(params, {"tokens": x_te})
        pred = jnp.argmax(logits[:, :-1].astype(jnp.float32), axis=-1)
        acc = jnp.mean((pred == x_te[:, 1:]).astype(jnp.float32))
        return {"train_cost": jnp.mean(self._per_example_ce(params, x_tr)),
                "test_accuracy": acc}

    def default_data(self, n_train: int = 512, n_test: int = 128,
                     seed: int = 0) -> TaskData:
        docs = synthetic.token_dataset(n_train + n_test, self.seq_len,
                                       self.cfg.vocab_size, seed=seed)
        x_tr, x_te = docs[:n_train], docs[n_train:]
        # tokens double as their own labels (the loss shifts internally);
        # sharing the array keeps one device copy per split
        return TaskData(x_tr, x_tr, x_te, x_te)


def transformer_task(arch: str = "llama3-8b", *, layers: int = 2,
                     d_model: int = 64, d_ff: int = 128, vocab: int = 128,
                     seq_len: int = 32) -> LMTask:
    """A reduced decoder-only LM (same family/wiring as ``arch``) sized
    for CPU-scale federated rounds."""
    cfg = reduced(get_config(arch), layers=layers, d_model=d_model,
                  d_ff=d_ff, vocab=vocab)
    return LMTask(cfg=cfg, seq_len=seq_len)


def chip_share_task(cfg: ModelConfig | str, *, num_layers: int, vocab: int,
                    seq_len: int) -> LMTask:
    """``cfg`` (or the registered architecture of that name) at its
    published widths and dtypes, cut only to one chip's share of a
    deployment: the first ``num_layers`` layers (the rest would be
    further pipeline stages) and a ``vocab``-token slice of the
    vocabulary (token ids, the tied embedding and the logits all over
    the slice).  The cut is recorded in the config's name, e.g.
    ``granite-8b[layers 1/36, vocab 6144/49152]``."""
    if isinstance(cfg, str):
        cfg = get_config(cfg)
    if not 0 < num_layers <= cfg.num_layers \
            or not 0 < vocab <= cfg.vocab_size:
        raise ValueError(
            f"a chip's share of {cfg.name} holds 1..{cfg.num_layers} layers "
            f"and 1..{cfg.vocab_size} tokens, not {num_layers} and {vocab}")
    cut = dataclasses.replace(
        cfg, num_layers=num_layers, vocab_size=vocab,
        name=f"{cfg.name}[layers {num_layers}/{cfg.num_layers}, "
             f"vocab {vocab}/{cfg.vocab_size}]")
    return LMTask(cfg=cut, seq_len=seq_len)
