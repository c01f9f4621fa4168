"""UC4 review query with DeepSeek-V2-Lite, cut to one pipeline stage, as
the LLM UDF.

The same query, data, eddy, Laminar and workers as ``review-smollm135m``
(this deployment subclasses that one): ``SELECT * FROM reviews WHERE
rating <= 1 AND LLM_is_food(tokens) > 0``, one-star reviews scored in
routing batches of 16, each row padded to 512 tokens. The model is the
program's (``build_llm_udf`` over ``models/deepseek.py``: latent attention
in the Pallas flash kernel, dropless routed experts in the Pallas grouped
expert kernel) at the published widths in bfloat16, five of the 27 layers
(layer 0 dense, MoE layers 1-4: one stage of a pipeline whose other stages
hold the rest), weights made on the device from the seed.

The check is ``review-smollm135m``'s, against ``refs/deepseek_v2.py``:

* ``rows_wrong`` (exact, 0): every finished query's rows against the
  one-star rows whose window score is above 0.
* ``score_gap``: the widest gap between a window score and the float32
  reference's, over a seeded sample of at least 256 scored rows. Its limit
  is set in the configuration file from readings at the scan's review
  lengths (``limit_readings``): the program's bfloat16 readings below it,
  the reference's with every product's operands in float8 above it. A
  score sums over a row's real positions, so both grow with the sample's
  longest review. Between the two lies what bfloat16 rounding does, a
  near-tied router score included: it can swap a token's 6th and 7th
  expert against the float32 reference.

The reference runs after the window, once the program's weights are
released: both copies in bfloat16 (11.4 GB) would not fit beside the
reference's work.
"""
from __future__ import annotations

import numpy as np

from chipbench import spec
from chipbench.harness import seed32
from chipbench.refs import deepseek_v2 as ref

SMOLLM = spec.deployment_module("review-smollm135m")

SMALL = {"hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
         "n_routed_experts": 8, "n_shared_experts": 2, "num_experts_per_tok": 3,
         "num_attention_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
         "qk_rope_head_dim": 8, "v_head_dim": 16, "num_hidden_layers": 3,
         "first_k_dense_replace": 1, "vocab_size": 256, "rms_norm_eps": 1e-6,
         "rope_theta": 10000.0, "norm_topk_prob": False, "routed_scaling_factor": 1.0}
REF_KEYS = tuple(SMALL) + ("rope_scaling",)


def model_config(s, name, dtype, source="", attention_impl="pallas"):
    """The program's ``ModelConfig`` of sizes ``s`` (the config.json keys)."""
    from repro.configs.base import ModelConfig, YarnScaling

    y = s["rope_scaling"]
    if s.get("q_lora_rank"):
        raise ValueError("q compression (q_lora_rank) is not implemented")
    if s["routed_scaling_factor"] != 1 or s["vocab_size"] % 256:
        raise ValueError("routed_scaling_factor 1 and a vocabulary of whole "
                         "256-row blocks are what the program runs")
    return ModelConfig(
        name=name, family="deepseek", num_layers=s["num_hidden_layers"],
        d_model=s["hidden_size"], num_heads=s["num_attention_heads"],
        num_kv_heads=s["num_attention_heads"],
        head_dim=s["qk_nope_head_dim"] + s["qk_rope_head_dim"],
        d_ff=s["intermediate_size"], vocab_size=s["vocab_size"],
        num_experts=s["n_routed_experts"], num_experts_per_tok=s["num_experts_per_tok"],
        moe_d_ff=s["moe_intermediate_size"], num_shared_experts=s["n_shared_experts"],
        first_dense_layers=s["first_k_dense_replace"],
        router_renormalize=s["norm_topk_prob"], kv_lora_rank=s["kv_lora_rank"],
        qk_nope_head_dim=s["qk_nope_head_dim"], qk_rope_head_dim=s["qk_rope_head_dim"],
        v_head_dim=s["v_head_dim"], rope_theta=float(s["rope_theta"]),
        rope_scaling=YarnScaling(
            factor=float(y["factor"]),
            original_max_position_embeddings=int(y["original_max_position_embeddings"]),
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=float(y["mscale"]), mscale_all_dim=float(y["mscale_all_dim"])),
        norm_eps=s["rms_norm_eps"], tie_embeddings=False, dtype=dtype,
        attention_impl=attention_impl, source=source)


# --------------------------------------------------------------------------- #
# required work of one row of n real tokens (causal, no padding)              #
# --------------------------------------------------------------------------- #
def _qk(s):
    return s["qk_nope_head_dim"] + s["qk_rope_head_dim"]


def _moe_layers(s):
    return s["num_hidden_layers"] - s["first_k_dense_replace"]


def dense_flops_per_token(s) -> int:
    """Everything but attention's token pairs, 2 FLOPs per weight a token
    uses: MLA projections, the dense layer's SwiGLU, the router, the shared
    experts, its 6 routed experts in each MoE layer, and the head."""
    d, h, r = s["hidden_size"], s["num_attention_heads"], s["kv_lora_rank"]
    nope, rope, dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    fe, k, e = s["moe_intermediate_size"], s["num_experts_per_tok"], s["n_routed_experts"]
    attn = d * h * (nope + rope) + d * (r + rope) + r * h * (nope + dv) + h * dv * d
    dense = 3 * d * s["intermediate_size"]
    moe = d * e + 3 * d * fe * s["n_shared_experts"] + k * 3 * d * fe
    nd = s["first_k_dense_replace"]
    return 2 * (s["num_hidden_layers"] * attn + nd * dense + _moe_layers(s) * moe
                + d * s["vocab_size"])


def attention_flops(s, n: np.ndarray) -> np.ndarray:
    """QK^T (192 wide) and PV (128 wide) over the n(n+1)/2 causal pairs:
    2 (192 + 128) FLOPs per pair, every head and layer."""
    pairs = n * (n + 1) // 2
    return (2 * (_qk(s) + s["v_head_dim"]) * s["num_attention_heads"]
            * s["num_hidden_layers"] * pairs)


def attention_bytes(s, n: np.ndarray) -> np.ndarray:
    """Q and K (192 wide), V and O (128 wide) read or written once, in
    bfloat16, every head and layer."""
    width = 2 * _qk(s) + 2 * s["v_head_dim"]
    return 2 * s["num_hidden_layers"] * n * s["num_attention_heads"] * width


def expert_ffn_work(s, n_batch: np.ndarray):
    """(FLOPs, bytes) of the grouped expert kernel over batches of
    ``n_batch`` real tokens: 3 x 2 x 2048 x 1408 FLOPs per real assignment;
    bytes of each assignment's row in and out (bfloat16), plus the weights
    of min(64, 6 n) experts per batch and MoE layer. At this traffic's ~500
    real tokens a batch every expert is hit (a miss has probability about
    e^-47), so that is the weights the kernel really reads."""
    d, fe = s["hidden_size"], s["moe_intermediate_size"]
    k, e, layers = s["num_experts_per_tok"], s["n_routed_experts"], _moe_layers(s)
    asg = k * n_batch
    flops = 3 * 2 * d * fe * asg * layers
    nbytes = layers * (asg * d * 2 * 2 + np.minimum(e, asg) * 3 * d * fe * 2)
    return float(flops.sum()), float(nbytes.sum())


class Deployment(SMOLLM.Deployment):
    def __init__(self, config, mix, seed, small=False):
        super().__init__(config, mix, seed, small)
        self.model_sizes = dict(SMALL, rope_scaling=self.sizes["rope_scaling"]) \
            if small else {k: self.sizes[k] for k in REF_KEYS}
        self.params = None

    # ---------------------------------------------------------------- set-up
    def make_model(self):
        from repro.core.udf import Predicate
        from repro.launch.serve import build_llm_udf
        from chipbench.spans import spanned

        s = dict(self.sizes, **self.model_sizes)
        cfg = model_config(s, self.config["name"], self.sizes["serve_dtype"],
                           self.config["source"])
        self.params = ref.make_weights(self.model_sizes, seed32(self.seed, 2))
        udf = build_llm_udf(cfg=cfg, params=self.params)
        self.pred = spanned(Predicate("LLM_is_food", udf, compare=lambda x: x > 0),
                            self.rec)
        self.predicates = [self.pred]

    def use_control(self):
        """Put the reference, its products in float8, in the model's place,
        over the program's weights (the same seed makes the same ones)."""
        weights = self.params

        def control(d):
            toks = np.asarray(d["tokens"])
            rows = [t[t > 0] for t in toks]
            return ref.scores(weights, rows, self.model_sizes,
                              dot=ref.fp8_dot).astype(np.float32)

        self.pred.udf.fn = control

    # ---------------------------------------------------------------- after
    def close(self):
        super().close()
        self.params = None

    def required_work(self):
        n_rows = [(np.asarray(d["tokens"]) > 0).sum(1) for d, _, _ in self.rec.sample]
        n = np.concatenate(n_rows or [np.zeros(0, int)])
        n_batch = np.array([x.sum() for x in n_rows], np.int64)
        s = self.model_sizes
        att_f = attention_flops(s, n).sum()
        return {"flops": float(dense_flops_per_token(s) * n.sum() + att_f),
                "kernels": {"flash_attention": (float(att_f),
                                                float(attention_bytes(s, n).sum())),
                            "expert_ffn": expert_ffn_work(s, n_batch)},
                "experts": s["n_routed_experts"]}

    def reference_scores(self, rows, dot=ref.f32_dot):
        """The plain float32 reference over pool rows ``rows``."""
        weights = ref.make_weights(self.model_sizes, seed32(self.seed, 2))
        return ref.scores(weights, [self.pool.row(x) for x in rows],
                          self.model_sizes, dot=dot)
