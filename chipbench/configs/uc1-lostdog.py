"""UC1 lost-dog query: ``DogBreedClassifier = 'great dane' AND
DogColorClassifier = 'black'`` over 224x224 dog crops.

The breed classifier is the program's planted classifier
(``udfs.planted_classifier``: XLA HSV work over the crop, the planted label
as its answer), standing in for a breed model. The color classifier is the
Pallas HSV kernel, the program's ``udfs.color_predicate`` as it is. Its
function drops the kernel's histograms, so the deployment wraps
``ops.hsv_color_classify``, which that function looks up at each call, in
a spy that leaves the histograms for the check on the calling thread and
returns the kernel's result unchanged. The eddy runs the ``hydro`` policy.

Crops come from a pool of distinct crops made in set-up (``pool_crops``
black and as many of other colors); each query draws its black and other
crops from the pool without repeats and gives every use a fresh row id.
Queries may take any size up to the pool's.

The check, after the window:

* ``rows_wrong``: for every finished query, the rows the service returned
  against the rows whose planted breed is the target and whose float32
  reference histogram's largest bucket is black. Exact, over all rows.
* ``hist_gap_px``: the widest gap, in pixels, between a histogram the
  kernel returned in the window and the float32 reference's, over a
  seeded sample of ``SAMPLE_CALLS`` calls of the color UDF.
"""
from __future__ import annotations

import numpy as np

from chipbench import deploy
from chipbench.data import video
from chipbench.harness import seed32
from chipbench.refs import hsv as ref
from chipbench.spans import Recorder, ThreadStash, source_spans, spanned

SAMPLE_CALLS = 16
SMALL_CROP = 32
SMALL_POOL = 40
BLACK = ref.COLOR_NAMES.index("black")
PIXEL_FLOPS = 134  # HSV (16), nine range tests (6 compares, 6 ands each), counting (10)


def hsv_work(crops: int, size: int):
    """Required FLOPs and HBM bytes of ``crops`` HSV histograms: each pixel's
    three float32 channels read once."""
    px = crops * size * size
    return float(PIXEL_FLOPS * px), float(12 * px)


class Deployment(deploy.Deployment):
    def __init__(self, config, mix, seed, small=False):
        super().__init__(config, mix, seed, small)
        self.size = SMALL_CROP if small else self.sizes["crop_size"]
        self.pool_size = SMALL_POOL if small else self.sizes["pool_crops"]
        self.batch_rows = self.sizes["batch_rows"]
        self.rec_breed = Recorder(seed32(seed, 4), keep=0)
        self.rec_color = Recorder(seed32(seed, 5), keep=SAMPLE_CALLS)

    # ---------------------------------------------------------------- set-up
    def make_model(self):
        from repro import udfs
        from repro.kernels import ops

        breed = udfs.planted_classifier(
            "DogBreedClassifier", video.BREEDS.index(self.sizes["breed"]),
            label_column="breed_gt", pixel_column="crop")
        color = udfs.color_predicate(self.sizes["color"], size=self.size,
                                     impl="pallas", name="DogColorClassifier")
        stash = ThreadStash()
        kernel = ops.hsv_color_classify

        def keep_histograms(*args, **kwargs):
            hist, label = kernel(*args, **kwargs)
            stash.value = hist
            return hist, label

        ops.hsv_color_classify = keep_histograms
        self._unspy = lambda: setattr(ops, "hsv_color_classify", kernel)
        self.stash = stash
        # the breed stand-in calls the same op (on XLA): its call clears
        # the stash, and its recorder keeps nothing
        self.breed = spanned(breed, self.rec_breed, extra=stash.take)
        self.color = spanned(color, self.rec_color, extra=stash.take)
        self.predicates = [self.breed, self.color]

    def use_control(self):
        """Put the reference, computed in bfloat16, in the kernel's place."""

        def control(d):
            hist = ref.control_histograms(np.asarray(d["crop"]))
            self.stash.value = hist
            return hist.argmax(1)

        self.color.udf.fn = control

    def make_data(self, plan):
        sizes = deploy.with_warm_query(plan)
        rng = np.random.default_rng([self.seed, 5])
        others = [c for c in video.COLORS if c != "black"]
        p = np.array([video.COLOR_PROBS[video.COLORS.index(c)] for c in others])
        colors = (["black"] * self.pool_size
                  + list(rng.choice(others, self.pool_size, p=p / p.sum())))
        self.crops = video.make_crops(colors, self.size, rng)
        self.start = np.concatenate([[0], np.cumsum(sizes)])
        self.index = [None] * len(sizes)
        self.breed_gt = [None] * len(sizes)
        for size in np.unique(sizes):
            queries = np.nonzero(sizes == size)[0]
            index, breed = self._draw(int(size), len(queries), rng)
            for j, q in enumerate(queries):
                self.index[q], self.breed_gt[q] = index[j], breed[j]

    def _draw(self, size, n, rng):
        """Crop indices and breeds of ``n`` queries of ``size`` rows, with
        the mix's exact shares of black crops and great danes."""
        k_black = int(round(self.data["color_share"] * size))
        k_dane = int(round(self.data["breed_share"] * size))
        if max(k_black, size - k_black) > self.pool_size:
            raise ValueError("the crop pool is smaller than one query's draw")
        pick = lambda k: rng.permuted(np.tile(np.arange(self.pool_size), (n, 1)),
                                      axis=1)[:, :k]
        idx = np.concatenate([pick(k_black), self.pool_size + pick(size - k_black)], 1)
        others = [b for b in range(len(video.BREEDS)) if b != 0]
        bp = np.array([video.BREED_PROBS[b] for b in others])
        breed = np.concatenate([np.zeros((n, k_dane), np.int64),
                                rng.choice(others, (n, size - k_dane), p=bp / bp.sum())], 1)
        return rng.permuted(idx, axis=1), rng.permuted(breed, axis=1)

    def warm_batches(self, plan):
        for b in deploy.buckets(range(1, self.batch_rows + 1)):
            cols = {"crop": self.crops[:b], "breed_gt": np.zeros(b, np.int64)}
            yield self.breed, cols
            yield self.color, cols

    # ---------------------------------------------------------------- window
    def _ids(self, i):
        return np.arange(self.start[i], self.start[i + 1], dtype=np.int64)

    def _chunks(self, i):
        ids = self._ids(i)
        for lo in range(0, len(ids), self.batch_rows):
            sl = slice(lo, lo + self.batch_rows)
            yield {"crop": self.crops[self.index[i][sl]],
                   "breed_gt": self.breed_gt[i][sl], "_row_id": ids[sl]}

    def query(self, i):
        from repro.core.plan import Query, batches_of
        from repro.core.policies import EDDY_POLICIES

        q = Query(source=source_spans("crops", self._chunks(i)),
                  predicates=self.predicates, batch_rows=self.batch_rows)
        return self.predicates, batches_of(q), dict(
            policy=EDDY_POLICIES["hydro"](), max_workers=4)

    # ---------------------------------------------------------------- after
    def close(self):
        super().close()
        self._unspy()
        self.breed = self.color = self.stash = None

    def required_work(self):
        return {"kernels": {"hsv_color": hsv_work(self.rec_color.rows, self.size)}}

    def check(self, records):
        hists = ref.histograms(self.crops)
        top2 = np.sort(hists, 1)[:, -2:]
        limit = self.sizes["limits"]["hist_gap_px"]
        px = self.size * self.size
        # a crop whose two largest buckets lie within the histogram limit of
        # each other may take either label; it is left out of the rows
        settled = top2[:, 1] - top2[:, 0] > 2 * limit / px
        is_black = hists.argmax(1) == BLACK
        wrong = 0
        for r in records:
            if not r.done:
                continue
            ids = self._ids(r.index)
            crop = self.index[r.index]
            want = (self.breed_gt[r.index] == 0) & is_black[crop]
            keep = settled[crop]
            got = r.report.row_ids[np.isin(r.report.row_ids, ids[keep])]
            wrong += deploy.multiset_diff(got, ids[keep & want])
            wrong += int(np.isin(r.report.row_ids, ids, invert=True).sum())
        gaps = []
        for data, _, hist in self.rec_color.sample:
            if hist is None:
                continue
            crops = np.asarray(data["crop"])
            got = np.asarray(hist)[:len(crops)]
            gaps.append(np.abs(got - ref.histograms(crops)).max() * px)
        gap = float(max(gaps)) if gaps else float("nan")
        return {"rows_wrong": (float(wrong), 0.0), "hist_gap_px": (gap, limit)}
