"""UC4 review query with SmolLM-135M as the LLM UDF.

``SELECT * FROM reviews WHERE rating <= 1 AND LLM_is_food(tokens) > 0``:
the scan pushes ``rating <= 1`` down, and the model scores the one-star
reviews in routing batches of 16, each row padded to 512 tokens. The
model is the program's (``build_llm_udf`` over ``models/transformer.py``,
attention in the Pallas flash kernel) at the published widths in
bfloat16, with weights made on the device from the seed.

The check, after the window:

* ``rows_wrong``: for every finished query, the rows the service returned
  against the one-star rows whose score, as the UDF returned it in the
  window, is above 0 (a one-star row the UDF never scored counts too).
  Exact: it covers the scan, executor, eddy, Laminar and workers.
* ``score_gap``: the widest gap between a score the UDF returned in the
  window and the float32 reference's score of the same review, over a
  sample drawn from the seed of the finished queries (the largest always
  among them), at least ``SAMPLE_ROWS`` rows. It covers the model, the
  flash kernel and the transfers.
"""
from __future__ import annotations

import numpy as np

from chipbench import deploy
from chipbench.data import reviews as rv
from chipbench.harness import seed32
from chipbench.refs import smollm as ref
from chipbench.spans import Recorder, source_spans, spanned

SAMPLE_ROWS = 256
SMALL = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
         "rms_norm_eps": 1e-5, "rope_theta": 10000.0}
SMALL_MAX_LEN = 128
REF_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "num_hidden_layers", "vocab_size",
            "rms_norm_eps", "rope_theta")


# --------------------------------------------------------------------------- #
# required work of one row of n real tokens (causal, no padding)              #
# --------------------------------------------------------------------------- #
def _dims(s):
    d, h, kv = s["hidden_size"], s["num_attention_heads"], s["num_key_value_heads"]
    return d, h, kv, d // h, s["intermediate_size"], s["num_hidden_layers"], s["vocab_size"]


def dense_flops_per_token(s) -> int:
    """Projections, MLP and the tied LM head: 2 FLOPs per weight."""
    d, h, kv, hd, f, layers, v = _dims(s)
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return 2 * (layers * per_layer + d * v)


def attention_flops(s, n: np.ndarray) -> np.ndarray:
    """QK^T and PV over the n(n+1)/2 causal pairs, every head and layer."""
    d, h, kv, hd, f, layers, v = _dims(s)
    return 4 * hd * h * layers * (n * (n + 1) // 2)


def attention_bytes(s, n: np.ndarray) -> np.ndarray:
    """Q, K, V read and O written once, in bfloat16, every layer."""
    d, h, kv, hd, f, layers, v = _dims(s)
    return 2 * layers * n * (2 * h * hd + 2 * kv * hd)


class Deployment(deploy.Deployment):
    def __init__(self, config, mix, seed, small=False):
        super().__init__(config, mix, seed, small)
        self.model_sizes = SMALL if small else {k: self.sizes[k] for k in REF_KEYS}
        self.max_len = SMALL_MAX_LEN if small else self.sizes["max_len"]
        self.batch_rows = self.sizes["batch_rows"]
        self.rec = Recorder(seed32(seed, 4))

    # ---------------------------------------------------------------- set-up
    def make_model(self):
        from repro.configs.base import ModelConfig
        from repro.core.udf import Predicate
        from repro.launch.serve import build_llm_udf

        s = self.model_sizes
        cfg = ModelConfig(
            name=self.config["name"], family="dense",
            num_layers=s["num_hidden_layers"], d_model=s["hidden_size"],
            num_heads=s["num_attention_heads"],
            num_kv_heads=s["num_key_value_heads"], d_ff=s["intermediate_size"],
            vocab_size=s["vocab_size"], rope_theta=s["rope_theta"],
            norm_eps=s["rms_norm_eps"], tie_embeddings=True,
            dtype=self.sizes["serve_dtype"], attention_impl="pallas",
            source=self.config["source"])
        params = ref.make_weights(s, seed32(self.seed, 2))
        udf = build_llm_udf(cfg=cfg, params=params)
        self.pred = spanned(Predicate("LLM_is_food", udf, compare=lambda x: x > 0),
                            self.rec)
        self.predicates = [self.pred]

    def make_data(self, plan):
        sizes = deploy.with_warm_query(plan)
        rng = np.random.default_rng([self.seed, 5])
        self.start = np.concatenate([[0], np.cumsum(sizes)])
        self.pool = rv.make_pool(int(self.start[-1]), rng, self.max_len)
        share = self.data["one_star_share"]
        self.one_star = np.rint(share * sizes).astype(np.int64)
        self.ratings = np.concatenate([rv.ratings(int(n), int(k), rng)
                                       for n, k in zip(sizes, self.one_star)])

    def warm_batches(self, plan):
        sizes = deploy.batch_sizes(self.one_star, self.batch_rows)
        for b in deploy.buckets(sizes):
            yield self.pred, {"tokens": np.zeros((b, self.max_len), np.int32)}

    def use_control(self):
        """Put the reference, its products in float8, in the model's place."""
        weights = ref.make_weights(self.model_sizes, seed32(self.seed, 2))

        def control(d):
            toks = np.asarray(d["tokens"])
            rows = [t[t > 0] for t in toks]
            return ref.scores(weights, rows, self.model_sizes,
                              dot=ref.fp8_dot).astype(np.float32)

        self.pred.udf.fn = control

    # ---------------------------------------------------------------- window
    def _chunks(self, i):
        chunk = self.sizes["source_chunk_rows"]
        for lo in range(self.start[i], self.start[i + 1], chunk):
            hi = min(lo + chunk, self.start[i + 1])
            yield {"tokens": self.pool.padded(lo, hi),
                   "rating": self.ratings[lo:hi],
                   "_row_id": np.arange(lo, hi, dtype=np.int64)}

    def query(self, i):
        from repro.core.plan import Query, TrivialPredicate, batches_of
        from repro.core.policies import EDDY_POLICIES, DataAware

        q = Query(source=source_spans("review", self._chunks(i)),
                  predicates=[self.pred],
                  trivial=[TrivialPredicate("rating", "<=", 1)],
                  batch_rows=self.batch_rows)
        return [self.pred], batches_of(q), dict(
            policy=EDDY_POLICIES["cost"](), laminar_policy_factory=DataAware,
            max_workers=4)

    # ---------------------------------------------------------------- after
    def close(self):
        super().close()
        self.pred = None

    def _scored(self):
        """Real tokens (as bytes) -> score, for every row the UDF scored."""
        out = {}
        for data, scores, _ in self.rec.sample:
            toks = np.asarray(data["tokens"])
            n = (toks > 0).sum(1)
            for row, k, sc in zip(toks, n, np.asarray(scores)):
                out[row[:k].astype(np.uint8).tobytes()] = float(sc)
        return out

    def required_work(self):
        n = np.concatenate([(np.asarray(d["tokens"]) > 0).sum(1)
                            for d, _, _ in self.rec.sample] or [np.zeros(0, int)])
        s = self.model_sizes
        att_f = attention_flops(s, n).sum()
        return {"flops": float(dense_flops_per_token(s) * n.sum() + att_f),
                "kernels": {"flash_attention": (float(att_f),
                                                float(attention_bytes(s, n).sum()))}}

    def _one_star_rows(self, i):
        lo, hi = self.start[i], self.start[i + 1]
        return lo + np.nonzero(self.ratings[lo:hi] <= 1)[0]

    def check(self, records):
        scored = self._scored()
        key = lambda r: self.pool.tokens[self.pool.offsets[r]:self.pool.offsets[r + 1]].tobytes()
        done = [r for r in records if r.done]
        wrong = 0
        for r in done:
            rows = self._one_star_rows(r.index)
            scores = np.array([scored.get(key(x), np.nan) for x in rows])
            wrong += int(np.isnan(scores).sum())
            wrong += deploy.multiset_diff(r.report.row_ids, rows[scores > 0])

        rng = np.random.default_rng([self.seed, 3])
        sample, order = [], []
        if done:
            big = int(np.argmax([r.rows for r in done]))
            order = [big] + [int(j) for j in rng.permutation(len(done)) if j != big]
        for j in order:
            if len(sample) >= (16 if self.small else SAMPLE_ROWS):
                break
            sample.extend(self._one_star_rows(done[j].index))
        got = np.array([scored.get(key(x), np.nan) for x in sample])
        want = self.reference_scores(sample)
        gap = float(np.max(np.abs(got - want))) if sample else float("nan")
        return {"rows_wrong": (float(wrong), 0.0),
                "score_gap": (gap, self.sizes["limits"]["score_gap"])}

    def reference_scores(self, rows, dot=ref.f32_dot):
        """The plain float32 reference over pool rows ``rows``."""
        weights = ref.make_weights(self.model_sizes, seed32(self.seed, 2))
        return ref.scores(weights, [self.pool.row(x) for x in rows],
                          self.model_sizes, dot=dot)
