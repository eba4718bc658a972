"""Work counts of the program's dense transformer family, layer by layer,
as ``bench.counts`` reads them: a layer is six matrix products (q, k, v
and o, the MLP's in and out), two norm scales and a K/V cache row; every
layer alike.  The LM head is the embedding table, tied, or a table of its
own; the classifier's head is d × classes."""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Geometry:
    n_layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    gated: bool              # SwiGLU: wi is (d, 2·ff)
    n_classes: int = 0
    param_bytes: int = 2
    untied_head: bool = False    # an LM whose head is a table of its own

    @classmethod
    def from_config(cls, c: dict) -> "Geometry":
        d, h = c["hidden_size"], c["num_attention_heads"]
        prog = c.get("program", {})
        return cls(n_layers=c["num_hidden_layers"], d=d, heads=h,
                   kv_heads=c.get("num_key_value_heads", h),
                   head_dim=c.get("head_dim", d // h),
                   ff=c["intermediate_size"], vocab=c["vocab_size"],
                   gated=c["hidden_act"] == "silu",
                   n_classes=int(prog.get("n_classes", 0)),
                   param_bytes={"bfloat16": 2, "float16": 2,
                                "float32": 4}[c["torch_dtype"]],
                   untied_head=(prog.get("task", "lm") == "lm"
                                and not c.get("tie_word_embeddings", False)))

    def projections(self, l: int) -> list[tuple[int, int]]:
        """(d_in, d_out) of layer l's six matrix products."""
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        wi = 2 * self.ff if self.gated else self.ff
        return [(self.d, q), (self.d, kv), (self.d, kv), (q, self.d),
                (self.d, wi), (self.ff, self.d)]

    def matmul_flops(self, l: int) -> float:
        """A token's forward matrix FLOPs through layer l: two per weight
        of its products."""
        return 2.0 * sum(a * b for a, b in self.projections(l))

    def layer_params(self, l: int) -> int:
        """Every parameter of layer l: the products and two norm scales."""
        return sum(a * b for a, b in self.projections(l)) + 2 * self.d

    def attn_flops(self, l: int, keys: float) -> float:
        """Scores and the weighted sum for one query over ``keys`` keys."""
        return 4.0 * keys * self.heads * self.head_dim

    def cache_bytes(self, l: int) -> int:
        """K and V of one position of layer l."""
        return 2 * self.kv_heads * self.head_dim * self.param_bytes

    @property
    def head_params(self) -> int:
        """What a decode step reads besides the layers: the embedding table,
        the final norm, and an untied LM head's table."""
        return self.vocab * self.d * (2 if self.untied_head else 1) + self.d

    @property
    def lm_head_flops(self) -> float:
        """One token through the LM head."""
        return 2.0 * self.d * self.vocab

    @property
    def class_head_flops(self) -> float:
        """One pooled sequence through the classifier's head."""
        return 2.0 * self.d * self.n_classes
