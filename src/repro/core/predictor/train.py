"""Predictor training: pairwise (PARS), pointwise (L1), listwise (ListMLE).

Protocol follows the paper §IV: Adam, 5 epochs, batch 128, margin 1.0,
δ-filtered pairs for PARS. The paper fine-tunes pretrained BERT-base at
lr 2e-5; our from-scratch mini backbones use lr 3e-4 (DESIGN.md §8).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.predictor.backbones import (PredictorConfig, init_predictor,
                                            predictor_forward)
from repro.core.predictor.losses import (l1_pointwise_loss, listmle_loss,
                                         margin_ranking_loss, POINTWISE_SCALE)
from repro.core.predictor.metrics import kendall_tau_b
from repro.core.predictor.pairing import build_pairs
from repro.core.predictor.tokenizer import HashTokenizer
from repro.tracing import span
from repro.training.optimizer import Adam, apply_updates

PyTree = Any

METHODS = ("pairwise", "pointwise", "listwise")


@dataclass
class TrainSettings:
    method: str = "pairwise"
    epochs: int = 5
    batch_size: int = 128
    learning_rate: float = 3e-4
    margin: float = 1.0
    delta: float = 0.20           # min_length_difference threshold (0 = off)
    pairs_per_epoch: int = 6_400
    list_size: int = 16           # listwise group size
    seed: int = 0


@dataclass
class RankingPredictor:
    """Trained predictor: ``score()`` maps prompts → expected-length scores.

    Higher score ⇒ longer expected response ⇒ *lower* SJF priority.
    """
    cfg: PredictorConfig
    params: PyTree
    tokenizer: HashTokenizer
    method: str = "pairwise"
    _jit_fwd: Any = field(default=None, repr=False)

    def __post_init__(self):
        self._jit_fwd = jax.jit(
            functools.partial(predictor_forward, cfg=self.cfg))

    def score_tokens(self, tokens: np.ndarray) -> np.ndarray:
        return np.asarray(self._jit_fwd(self.params, tokens=jnp.asarray(tokens)))

    def score(self, prompts) -> np.ndarray:
        with span("pred.tokenize"):
            tokens = self.tokenizer.encode_batch(list(prompts))
        with span("pred.forward"):
            return self.score_tokens(tokens)

    # ------------------------------------------------------------ persistence
    def save(self, path: str) -> None:
        from repro.training.checkpoint import save_checkpoint
        meta = {"method": self.method, "backbone": self.cfg.backbone,
                **{k: getattr(self.cfg, k) for k in
                   ("vocab_size", "max_len", "d_model", "num_heads",
                    "num_layers", "d_ff")}}
        save_checkpoint(path, self.params, metadata=meta)

    @classmethod
    def load(cls, path: str) -> "RankingPredictor":
        import json
        from repro.core.predictor.backbones import (PredictorConfig,
                                                    init_predictor)
        from repro.training.checkpoint import load_checkpoint
        with open((path if path.endswith(".npz") else path + ".npz")
                  + ".json") as f:
            meta = json.load(f)["metadata"]
        cfg = PredictorConfig(
            backbone=meta["backbone"], vocab_size=meta["vocab_size"],
            max_len=meta["max_len"], d_model=meta["d_model"],
            num_heads=meta["num_heads"], num_layers=meta["num_layers"],
            d_ff=meta["d_ff"])
        like = init_predictor(jax.random.PRNGKey(0), cfg)
        params = load_checkpoint(path, like)
        tok = HashTokenizer(vocab_size=cfg.vocab_size, max_len=cfg.max_len)
        return cls(cfg=cfg, params=params, tokenizer=tok,
                   method=meta.get("method", "pairwise"))


def _make_loss(cfg: PredictorConfig, settings: TrainSettings):
    method = settings.method

    if method == "pairwise":
        def loss_fn(params, batch):
            s_a = predictor_forward(params, cfg, batch["tok_a"])
            s_b = predictor_forward(params, cfg, batch["tok_b"])
            return margin_ranking_loss(s_a, s_b, batch["y"], settings.margin)
    elif method == "pointwise":
        def loss_fn(params, batch):
            s = predictor_forward(params, cfg, batch["tokens"])
            return l1_pointwise_loss(s, batch["lengths"])
    elif method == "listwise":
        def loss_fn(params, batch):
            b, l, t = batch["tokens"].shape
            s = predictor_forward(params, cfg,
                                  batch["tokens"].reshape(b * l, t))
            return listmle_loss(s.reshape(b, l),
                                batch["lengths"].reshape(b, l))
    else:
        raise ValueError(f"unknown method {method!r}")
    return loss_fn


def train_predictor(prompts, lengths, *,
                    backbone: str = "bert",
                    settings: Optional[TrainSettings] = None,
                    tokenizer: Optional[HashTokenizer] = None,
                    pcfg: Optional[PredictorConfig] = None,
                    log_fn=None) -> RankingPredictor:
    """Train a ranking predictor on (prompt, ground-truth-length) data."""
    st = settings or TrainSettings()
    tok = tokenizer or HashTokenizer()
    cfg = pcfg or PredictorConfig(backbone=backbone, vocab_size=tok.vocab_size,
                                  max_len=tok.max_len)
    rng = np.random.default_rng(st.seed)
    tokens = tok.encode_batch(list(prompts))
    lengths = np.asarray(lengths, np.float32)

    params = init_predictor(jax.random.PRNGKey(st.seed), cfg)
    opt = Adam(learning_rate=st.learning_rate, clip_norm=1.0)
    opt_state = opt.init(params)
    loss_fn = _make_loss(cfg, st)

    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        return apply_updates(params, updates), opt_state, loss

    n = len(tokens)
    bs = st.batch_size
    for epoch in range(st.epochs):
        losses = []
        if st.method == "pairwise":
            ia, ib, y = build_pairs(lengths, rng, n_pairs=st.pairs_per_epoch,
                                    delta=st.delta)
            for i in range(0, len(ia) - bs + 1, bs):
                batch = {"tok_a": jnp.asarray(tokens[ia[i:i + bs]]),
                         "tok_b": jnp.asarray(tokens[ib[i:i + bs]]),
                         "y": jnp.asarray(y[i:i + bs])}
                params, opt_state, loss = step(params, opt_state, batch)
                losses.append(float(loss))
        elif st.method == "pointwise":
            perm = rng.permutation(n)
            for i in range(0, n - bs + 1, bs):
                sel = perm[i:i + bs]
                batch = {"tokens": jnp.asarray(tokens[sel]),
                         "lengths": jnp.asarray(lengths[sel])}
                params, opt_state, loss = step(params, opt_state, batch)
                losses.append(float(loss))
        else:  # listwise
            ls = st.list_size
            n_lists = max(1, bs // ls)
            perm = rng.permutation(n - n % (ls * n_lists))
            groups = perm.reshape(-1, n_lists, ls)
            for grp in groups:
                batch = {"tokens": jnp.asarray(tokens[grp]),
                         "lengths": jnp.asarray(lengths[grp])}
                params, opt_state, loss = step(params, opt_state, batch)
                losses.append(float(loss))
        if log_fn:
            log_fn(f"[{st.method}/{backbone}] epoch {epoch}: "
                   f"loss {np.mean(losses):.4f} ({len(losses)} steps)")

    return RankingPredictor(cfg=cfg, params=params, tokenizer=tok,
                            method=st.method)


def evaluate_tau(predictor: RankingPredictor, prompts, lengths) -> float:
    """Kendall τ_b between predicted scores and ground-truth lengths."""
    scores = predictor.score(prompts)
    return kendall_tau_b(scores, np.asarray(lengths))
