"""Peaks of the chip, and the work a step needs, counted from shapes.

The operations and bytes here are what the algorithm needs, worked out from
the configuration's sizes, not what the compiled program happens to do: a
program that reads the KV lane past the live tokens, or copies the pool,
shows as a lower share of the roofline, never as more work.
"""
from __future__ import annotations

from dataclasses import dataclass

#: Published peaks of one chip, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (system architecture page):
#: 197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB HBM per chip.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"flops": 197e12, "hbm_bytes_s": 819e9, "hbm_bytes": 16e9},
}
SOURCE = 'Google Cloud documentation, "TPU v5e"'


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a device that is not in the table is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peak table entry for device kind {device_kind!r}"
                       f" (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


@dataclass(frozen=True)
class Shapes:
    """The sizes a step's work depends on (from a configuration file)."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool             # gated MLP: three matrices, else two
    tied: bool              # the head is the embedding
    dtype_bytes: int = 2

    @classmethod
    def from_config(cls, c: dict) -> "Shapes":
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   gated=c["hidden_act"] == "silu",
                   tied=c["tie_word_embeddings"],
                   dtype_bytes={"bfloat16": 2, "float32": 4}[c["dtype"]])

    # ---------------------------------------------------------- parameters
    @property
    def layer_params(self) -> int:
        d, dh = self.d_model, self.head_dim
        attn = 2 * d * self.heads * dh + 2 * d * self.kv_heads * dh
        mlp = (3 if self.gated else 2) * d * self.d_ff
        norms = 4 * d                      # two LayerNorms: scale and bias
        return attn + mlp + norms

    @property
    def embed_params(self) -> int:
        return self.vocab * self.d_model

    @property
    def head_params(self) -> int:
        return self.vocab * self.d_model

    def weight_bytes(self) -> dict:
        b = self.dtype_bytes
        layers = self.layers * self.layer_params * b
        table = (self.embed_params + (0 if self.tied else self.head_params)
                 + 2 * self.d_model) * b      # + final LayerNorm
        return {"layers": layers, "embedding_and_head": table,
                "total": layers + table}

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * self.dtype_bytes

    # ---------------------------------------------------------------- work
    def attn_flops(self, context: int) -> float:
        """Score and value products of one query over ``context`` keys."""
        return 4.0 * self.layers * self.heads * self.head_dim * context

    def token_flops(self) -> float:
        """Matrix products of one token through the layers (no head)."""
        return 2.0 * self.layers * (self.layer_params - 4 * self.d_model)

    def head_flops(self) -> float:
        return 2.0 * self.head_params

    def decode(self, contexts) -> dict:
        """One decode step over a batch whose rows attend ``contexts`` keys:
        FLOPs, and bytes that must cross HBM (every weight the step uses
        once, the rows of the embedding it gathers, and each row's live KV)."""
        contexts = list(contexts)
        n = len(contexts)
        flops = sum(self.token_flops() + self.head_flops()
                    + self.attn_flops(c) for c in contexts)
        w = self.weight_bytes()
        head = self.head_params * self.dtype_bytes
        weights = w["layers"] + head + 2 * self.d_model * self.dtype_bytes
        gathered = n * self.d_model * self.dtype_bytes
        kv = sum(contexts) * self.kv_bytes_per_token
        return {"flops": flops, "bytes": weights + gathered + kv}

    def prefill(self, start: int, end: int, emits: bool) -> float:
        """FLOPs of prompt positions [start, end) of one request, attending
        causally to every earlier position; the head only where a token is
        emitted."""
        n = end - start
        if n <= 0:
            return 0.0
        keys = n * start + n * (n + 1) / 2            # sum of (p + 1)
        flops = n * self.token_flops() + 4.0 * self.layers * self.heads \
            * self.head_dim * keys
        return flops + (self.head_flops() if emits else 0.0)


def roofline_seconds(flops: float, nbytes: float, peak: dict):
    """Least time the chip could take, and which bound sets it."""
    t_flops = flops / peak["flops"]
    t_bytes = nbytes / peak["hbm_bytes_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
