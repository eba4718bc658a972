"""Configurations found by their block family.

The two committed dense configurations read what they read before the
harness found its keys, reference and work counts by family: the same
``ArchConfig`` field by field, and every count equal to its closed form
over one dense layer times L.  A configuration of another family comes in
as new files alone."""
import hashlib
import json
import shutil

import numpy as np
import pytest

from bench import counts, harness
from bench.reference import dense
from bench.tests.tiny import tiny_config

CONFIGS = ["xlmr-base", "smollm-360m", "xlmr-base.tiny", "smollm-360m.tiny"]


def _config(name: str) -> dict:
    if name.endswith(".tiny"):
        return tiny_config(name[:-len(".tiny")])
    return json.loads((harness.ROOT / "bench" / "configs"
                       / f"{name}.json").read_text())


def _arch(name, n_layers, d_model, n_heads, n_kv_heads, d_ff, vocab_size,
          mlp_act, rope_theta, tie, task, n_classes, source):
    from repro.configs.base import ArchConfig
    return ArchConfig(
        name=name, family="dense", n_layers=n_layers, d_model=d_model,
        n_heads=n_heads, n_kv_heads=n_kv_heads, d_ff=d_ff,
        vocab_size=vocab_size, head_dim=d_model // n_heads, source=source,
        mlp_act=mlp_act, qkv_bias=False, n_experts=0, n_shared_experts=0,
        top_k=0, first_dense=0, capacity_factor=1.25, router_aux_weight=0.01,
        use_mla=False, kv_lora_rank=0, qk_rope_dim=64, qk_nope_dim=128,
        v_head_dim=128, ssm_state=0, ssm_heads=0, ssm_expand=2,
        ssm_chunk=128, ssm_conv=4, ssm_groups=1, attn_every=0,
        n_enc_layers=0, enc_seq=0, n_prefix_tokens=0, sliding_window=0,
        rope_theta=rope_theta, norm_eps=1e-05, tie_embeddings=tie,
        logit_softcap=0.0, task=task, n_classes=n_classes, dtype="bfloat16")


XLMR = "https://huggingface.co/FacebookAI/xlm-roberta-base"
SMOLLM = "https://huggingface.co/HuggingFaceTB/SmolLM-360M"
TODAY = {
    "xlmr-base": _arch("xlmr-base", 12, 768, 12, 12, 3072, 250002,
                       "gelu_plain", 0.0, False, "classification", 10, XLMR),
    "smollm-360m": _arch("smollm-360m", 32, 960, 15, 5, 2560, 49152, "silu",
                         10000.0, True, "lm", 0, SMOLLM),
    "xlmr-base.tiny": _arch("xlmr-base", 3, 64, 4, 4, 128, 512, "gelu_plain",
                            0.0, False, "classification", 10, XLMR),
    "smollm-360m.tiny": _arch("smollm-360m", 8, 256, 4, 2, 384, 4096, "silu",
                              10000.0, True, "lm", 0, SMOLLM),
}


@pytest.mark.parametrize("name", CONFIGS)
def test_arch_config_is_as_before(name):
    assert harness.arch_config(_config(name)) == TODAY[name]


@pytest.mark.parametrize("name", CONFIGS)
def test_the_dense_family_is_found(name):
    config = _config(name)
    ref = harness.family_module(config, "reference")
    assert ref.segments(config) == [("blocks", config["num_hidden_layers"])]
    assert vars(ref.Arch.from_config(config)) == vars(
        dense.Arch.from_config(config))
    assert callable(ref.class_loss) and callable(ref.lm_logits)
    assert counts.geometry(config).n_layers == config["num_hidden_layers"]


# -- the counts as they were: one dense layer, times L ------------------------

class _OneLayer:
    def __init__(self, c: dict):
        d, h = c["hidden_size"], c["num_attention_heads"]
        self.n_layers, self.d, self.heads = c["num_hidden_layers"], d, h
        self.kv_heads = c.get("num_key_value_heads", h)
        self.head_dim = c.get("head_dim", d // h)
        self.ff, self.vocab = c["intermediate_size"], c["vocab_size"]
        self.gated = c["hidden_act"] == "silu"
        self.n_classes = int(c.get("program", {}).get("n_classes", 0))
        self.param_bytes = {"bfloat16": 2, "float16": 2,
                            "float32": 4}[c["torch_dtype"]]

    def projections(self):
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        wi = 2 * self.ff if self.gated else self.ff
        return [(self.d, q), (self.d, kv), (self.d, kv), (q, self.d),
                (self.d, wi), (self.ff, self.d)]

    @property
    def matmul_params(self):
        return sum(a * b for a, b in self.projections())

    @property
    def layer_params(self):
        return self.matmul_params + 2 * self.d

    def attn_flops(self, keys):
        return 4.0 * keys * self.heads * self.head_dim


def _fl_round_flops(g, masks, probed, *, tau, batch, seq, eval_rows):
    per_tok_fwd = 2.0 * g.matmul_params + g.attn_flops(seq)
    per_tok_act_bwd = 2.0 * g.matmul_params + 2.0 * g.attn_flops(seq)
    per_tok_wgrad = 2.0 * g.matmul_params
    head_fwd = 2.0 * g.d * g.n_classes
    L = g.n_layers
    upd_tok = tau * batch * seq
    total = 0.0
    for m in np.asarray(masks):
        sel = np.flatnonzero(m > 0)
        total += upd_tok * L * per_tok_fwd + tau * batch * head_fwd
        if sel.size:
            total += upd_tok * ((L - sel[0]) * per_tok_act_bwd
                                + sel.size * per_tok_wgrad)
    probe_tok = batch * seq
    total += probed * (probe_tok * L * (per_tok_fwd + per_tok_act_bwd
                                        + per_tok_wgrad) + batch * head_fwd)
    total += eval_rows * (seq * L * per_tok_fwd + head_fwd)
    return total


def _decode_step_counts(g, active, delta_layers=None):
    L, pb = g.n_layers, g.param_bytes
    kv_row = 2 * g.kv_heads * g.head_dim * pb
    flops = 0.0
    nbytes = (L * g.layer_params + g.vocab * g.d + g.d) * pb
    for user, pos in active:
        keys = pos + 1
        flops += L * (2.0 * g.matmul_params + g.attn_flops(keys))
        flops += 2.0 * g.d * g.vocab
        nbytes += L * kv_row * (keys + 1)
        if delta_layers is not None and user >= 0:
            k = len(delta_layers[user])
            flops += k * 2.0 * g.matmul_params
            nbytes += k * g.layer_params * pb
    return flops, nbytes


def _delta_matmul_counts(g, active, delta_layers):
    pb = g.param_bytes
    B = len(active)
    per_layer = np.zeros(g.n_layers, np.int64)
    for user, _ in active:
        if user >= 0:
            per_layer[np.asarray(delta_layers[user])] += 1
    out = []
    for n in per_layer:
        for a, b in g.projections():
            out.append((2.0 * B * a * b + 2.0 * n * a * b,
                        (B * a + a * b + n * a * b + B * b) * pb))
    return out


def _draws(L: int, seed: int):
    """Seeded masks (an empty row among them), active slots (free ones
    among them) and users' delta layers."""
    rng = np.random.default_rng(seed)
    masks = (rng.random((8, L)) < 0.3).astype(np.float32)
    masks[3] = 0
    layers = [np.sort(rng.choice(L, min(2, L), replace=False))
              for _ in range(6)]
    active = [(int(u), int(p)) for u, p in
              zip(rng.integers(-1, 6, 8), rng.integers(0, 300, 8))]
    return masks, active, layers


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               np.asarray(want, np.float64), rtol=1e-12,
                               atol=0)


COUNTS = ["fl_round_flops", "masked_update_bytes", "layer_grad_norm_bytes",
          "decode_step_counts", "delta_matmul_counts"]


@pytest.mark.parametrize("fn", COUNTS)
@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("seed", [0, 2 ** 31 + 7])
def test_counts_are_as_before(name, fn, seed):
    config = _config(name)
    g, old = counts.geometry(config), _OneLayer(config)
    masks, active, layers = _draws(old.n_layers, seed)
    if fn == "fl_round_flops":
        kw = dict(tau=2, batch=8, seq=128, eval_rows=16)
        _close(counts.fl_round_flops(g, masks, 5, **kw),
               _fl_round_flops(old, masks, 5, **kw))
    elif fn == "masked_update_bytes":
        _close(counts.masked_update_bytes(g, masks, 2),
               old.layer_params * float(masks.sum()) * 2 * 3
               * old.param_bytes)
    elif fn == "layer_grad_norm_bytes":
        _close(counts.layer_grad_norm_bytes(g, 5),
               5 * old.n_layers * old.layer_params * old.param_bytes)
    elif fn == "decode_step_counts":
        _close(counts.decode_step_counts(g, active, layers),
               _decode_step_counts(old, active, layers))
        _close(counts.decode_step_counts(g, active, None),
               _decode_step_counts(old, active, None))
    else:
        _close(counts.delta_matmul_counts(g, active, layers),
               _delta_matmul_counts(old, active, layers))


def test_an_untied_lm_head_is_read_once_more():
    """A dense LM whose head is a table of its own reads it besides the
    embedding table; a classifier's head is its class head alone."""
    tied = _config("smollm-360m.tiny")
    untied = dict(tied, tie_word_embeddings=False)
    active = [(-1, 4), (-1, 9)]
    f0, b0 = counts.decode_step_counts(counts.geometry(tied), active)
    f1, b1 = counts.decode_step_counts(counts.geometry(untied), active)
    assert f1 == f0
    assert b1 - b0 == untied["vocab_size"] * untied["hidden_size"] * 2
    assert counts.geometry(_config("xlmr-base")).head_params == 250002 * 768 + 768


# -- what the harness refuses ----------------------------------------------

def test_a_program_key_that_names_no_field_is_refused():
    config = dict(_config("xlmr-base.tiny"))
    config["program"] = dict(config["program"], n_expert=8)
    with pytest.raises(harness.BenchError, match="n_expert"):
        harness.arch_config(config)


def test_an_unknown_activation_is_refused():
    with pytest.raises(harness.BenchError, match="relu2"):
        harness.arch_config(dict(_config("xlmr-base.tiny"),
                                 hidden_act="relu2"))


def test_a_field_no_key_gives_is_refused():
    config = {k: v for k, v in _config("xlmr-base.tiny").items()
              if k != "intermediate_size"}
    with pytest.raises(harness.BenchError, match="d_ff"):
        harness.arch_config(config)


def test_the_program_block_wins_over_the_table():
    config = dict(_config("smollm-360m.tiny"),
                  program={"family": "dense", "task": "lm", "d_ff": 96,
                           "n_kv_heads": 1})
    cfg = harness.arch_config(config)
    assert (cfg.d_ff, cfg.n_kv_heads, cfg.n_heads) == (96, 1, 4)


# -- a family of its own, by new files alone --------------------------------

TINY_DSV2 = {
    "name": "tiny-dsv2", "source": "https://huggingface.co/deepseek-ai/"
    "DeepSeek-V2-Lite/blob/main/config.json", "model_type": "deepseek_v2",
    "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 256,
    "moe_intermediate_size": 32, "vocab_size": 512, "hidden_act": "silu",
    "n_routed_experts": 8, "n_shared_experts": 2, "num_experts_per_tok": 2,
    "first_k_dense_replace": 1, "kv_lora_rank": 32, "q_lora_rank": None,
    "qk_rope_head_dim": 16, "qk_nope_head_dim": 32, "v_head_dim": 32,
    "rms_norm_eps": 1e-06, "rope_theta": 10000, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
    "program": {"family": "stubmoe", "use_mla": True, "d_ff": 32}}

STUB_REFERENCE = '''
from dataclasses import dataclass


@dataclass(frozen=True)
class Arch:
    n_layers: int

    @classmethod
    def from_config(cls, c):
        return cls(c["num_hidden_layers"])


def segments(c):
    first = c["first_k_dense_replace"]
    return [("dense0", first), ("blocks", c["num_hidden_layers"] - first)]


def class_loss(params, batch, a, lowp=None):
    return 0.0


def lm_logits(params, tokens, a, lowp=None, blocks=None):
    return None
'''

STUB_GEOMETRY = '''
from dataclasses import dataclass


@dataclass(frozen=True)
class Geometry:
    n_layers: int
    param_bytes: int = 2

    @classmethod
    def from_config(cls, c):
        return cls(c["num_hidden_layers"])

    def layer_params(self, l):
        return 1000 if l == 0 else 100      # a dense layer, then experts
'''


def _tree(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_new_family_comes_in_as_new_files(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(harness.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _tree(tmp_path)
    bench = tmp_path / "bench"
    (bench / "configs" / "tiny-dsv2.json").write_text(json.dumps(TINY_DSV2))
    (bench / "reference" / "stubmoe.py").write_text(STUB_REFERENCE)
    (bench / "geometry" / "stubmoe.py").write_text(STUB_GEOMETRY)
    (bench / "tests" / "tiny_sizes" / "configs" / "tiny-dsv2.json").write_text(
        json.dumps({"vocab_size": 256}))

    config = tiny_config("tiny-dsv2", root=tmp_path)
    assert config["vocab_size"] == 256
    cfg = harness.arch_config(config)
    assert (cfg.family, cfg.n_experts, cfg.n_shared_experts, cfg.top_k,
            cfg.first_dense, cfg.kv_lora_rank, cfg.qk_rope_dim,
            cfg.qk_nope_dim, cfg.v_head_dim, cfg.use_mla, cfg.d_ff,
            cfg.tie_embeddings) == ("stubmoe", 8, 2, 2, 1, 32, 16, 32, 32,
                                    True, 32, False)

    ref = harness.family_module(config, "reference", root=tmp_path)
    assert ref.segments(config) == [("dense0", 1), ("blocks", 3)]
    assert harness.segment_slices(ref.segments(config)) == {
        "dense0": slice(0, 1), "blocks": slice(1, 4)}
    pos, rows = harness.split_layers(ref.segments(config), [0, 2])["blocks"]
    assert pos.tolist() == [1] and rows.tolist() == [1]
    g = counts.geometry(config, root=tmp_path)
    assert type(g).__module__.endswith("stubmoe")
    assert counts.layer_grad_norm_bytes(g, 3) == 3 * (1000 + 3 * 100) * 2
    masks = np.array([[1, 0, 1, 0], [0, 1, 1, 1]], np.float32)
    assert counts.masked_update_bytes(g, masks, 2) == 2 * (1100 + 300) * 3 * 2

    with pytest.raises(harness.BenchError, match="n_routed"):
        harness.arch_config(dict(config, program={"n_routed": 8}))
    with pytest.raises(harness.BenchError,
                       match=r"bench/geometry/nosuch\.py"):
        harness.family_module(dict(config, program={"family": "nosuch"}),
                              "geometry", root=tmp_path)

    after = _tree(tmp_path)
    assert {p: after[p] for p in before} == before
    assert sorted(map(str, set(after) - set(before))) == [
        "bench/configs/tiny-dsv2.json", "bench/geometry/stubmoe.py",
        "bench/reference/stubmoe.py",
        "bench/tests/tiny_sizes/configs/tiny-dsv2.json"]
