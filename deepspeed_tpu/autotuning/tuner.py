"""Tuner strategies (reference ``autotuning/tuner/``): grid / random /
model-based search over experiment lists.  The reference's XGBoost cost
model becomes a ridge-regression-on-features model (no xgboost dependency;
the feature space is small — batch/ZeRO knobs plus the comm surface).

Two extensions over the reference:

* ``mode`` — "max" (throughput-like metrics) or "min" (latency /
  step_time): the comm autotuner minimizes measured step time.
* ``tie_breaker`` — a secondary result key (the comm loop uses
  ``exposed_comm_frac``): when two candidates land within ``tie_rtol``
  relative distance on the primary metric, the lower tie-breaker wins —
  between two configs with indistinguishable step time, prefer the one
  that hides more communication (it degrades more gracefully when the
  real model's compute/comm ratio shifts).  Without a tie_breaker the
  comparison is the reference's strict better-than.
"""

import random as _random

import numpy as np

#: payload bits per element of each wire format — the cost model's view of
#: "how aggressive is this config's quantization"
WIRE_BITS = {"fp32": 32, "fp12": 12, "int8": 8, "fp8": 8, "fp6": 6,
             "int4": 4}


class BaseTuner:
    """Reference ``tuner/base_tuner.py:13``: iterate experiments, track best."""

    def __init__(self, exps, runner, metric="throughput", mode="max",
                 tie_breaker=None, tie_rtol=0.02):
        if mode not in ("max", "min"):
            raise ValueError(f"tuner mode {mode!r} must be 'max' or 'min'")
        self.all_exps = list(exps)
        self.runner = runner
        self.metric = metric
        self.mode = mode
        self.tie_breaker = tie_breaker
        self.tie_rtol = tie_rtol
        self.best_exp = None
        self.best_metric_val = None
        self.best_tie_val = None

    def has_next(self):
        return len(self.all_exps) > 0

    def next_batch(self, sample_size=1):
        raise NotImplementedError

    def _beats_best(self, val, tie):
        if self.best_metric_val is None:
            return True
        sign = 1.0 if self.mode == "max" else -1.0
        gain = (val - self.best_metric_val) * sign
        if self.tie_breaker is None:
            return gain > 0
        margin = abs(self.best_metric_val) * self.tie_rtol
        if gain > margin:
            return True
        if gain >= -margin and tie is not None and \
                self.best_tie_val is not None and tie < self.best_tie_val:
            return True          # statistical tie: lower tie-breaker wins
        return False

    def update(self, exps, results):
        sign = 1.0 if self.mode == "max" else -1.0
        for exp, res in zip(exps, results):
            val = None if res is None else res.get(self.metric)
            exp["result"] = res
            if val is None:
                continue
            tie = res.get(self.tie_breaker) if self.tie_breaker else None
            if self._beats_best(val, tie):
                self.best_tie_val = tie
                self.best_exp = exp
            # the margin anchor stays pinned to the extreme primary value
            # ever measured — NOT the tie-broken winner's value.  Otherwise
            # chained within-margin ties would ratchet the baseline
            # arbitrarily far from the true best, and the returned config
            # could exceed tie_rtol of the measured minimum.
            if self.best_metric_val is None or \
                    (val - self.best_metric_val) * sign > 0:
                self.best_metric_val = val

    def tune(self, sample_size=1, n_trials=1000, early_stopping=None):
        trials, since_best = 0, 0
        while self.has_next() and trials < n_trials:
            batch = self.next_batch(sample_size)
            results = [self.runner(exp) for exp in batch]
            prev_best = self.best_exp
            self.update(batch, results)
            trials += len(batch)
            since_best = 0 if self.best_exp is not prev_best else \
                since_best + len(batch)
            if early_stopping and since_best >= early_stopping:
                break
        return self.best_exp


class GridSearchTuner(BaseTuner):
    """Reference ``index_based_tuner.py:27``: in-order exhaustive."""

    def next_batch(self, sample_size=1):
        batch = self.all_exps[:sample_size]
        self.all_exps = self.all_exps[sample_size:]
        return batch


class RandomTuner(BaseTuner):
    """Reference ``index_based_tuner.py:11``: uniform without replacement."""

    def next_batch(self, sample_size=1):
        k = min(sample_size, len(self.all_exps))
        batch = _random.sample(self.all_exps, k)
        for b in batch:
            self.all_exps.remove(b)
        return batch


def featurize_config(cfg):
    """Numeric feature vector of a candidate ``ds_config`` — the batch/ZeRO
    trinity the reference models plus the comm_optimizations surface the
    closed loop searches (wire aggressiveness, hierarchy, size floor,
    overlap bucketing in both directions)."""
    z = cfg.get("zero_optimization", {}).get("stage", 0)
    mbs = cfg.get("train_micro_batch_size_per_gpu", 1)
    gas = cfg.get("gradient_accumulation_steps", 1)
    co = cfg.get("comm_optimizations") or {}
    ov = co.get("overlap") or {}
    pf = ov.get("prefetch") or {}
    ladder = co.get("wire_dtype_by_size")
    quantizing = bool(co.get("enabled")) and (
        co.get("quantized_gradients") or co.get("quantized_weights"))
    if not quantizing:
        wire_bits = 32.0
    elif ladder:
        # one rung-parsing implementation — the same normalization the
        # engine dispatches on (loud on malformed rungs)
        from ..comm.collectives import build_wire_ladder
        rungs = build_wire_ladder(ladder) or ()
        bits = [WIRE_BITS.get(w, 32) for _, w in rungs]
        wire_bits = float(np.mean(bits)) if bits else 32.0
    else:
        wire_bits = float(WIRE_BITS.get(co.get("wire_dtype", "int8"), 32))
    return [
        float(z),
        float(np.log2(max(mbs, 1))),
        float(gas),
        1.0 if co.get("enabled") else 0.0,
        1.0 if co.get("hierarchical_allreduce") else 0.0,
        wire_bits,
        float(np.log2(1.0 + co.get("min_message_size", 0))),
        1.0 if ov.get("enabled") else 0.0,
        float(np.log2(1.0 + (ov.get("bucket_mb") or 0.0))),
        float(ov.get("max_inflight", 0) if ov.get("enabled") else 0),
        1.0 if pf.get("enabled") else 0.0,
        float(np.log2(1.0 + (pf.get("bucket_mb") or 0.0))),
    ]


class ModelBasedTuner(BaseTuner):
    """Reference ``model_based_tuner.py:19``: fit a cost model on measured
    points, propose the predicted-best next."""

    _MIN_FIT = 3

    def __init__(self, exps, runner, metric="throughput", mode="max",
                 tie_breaker=None, tie_rtol=0.02, tuning_space=None):
        super().__init__(exps, runner, metric, mode=mode,
                         tie_breaker=tie_breaker, tie_rtol=tie_rtol)
        self._X, self._y = [], []            # live measurements

    def _featurize(self, exp):
        return featurize_config(exp["ds_config"])

    def _predict(self, exp):
        if len(self._y) < self._MIN_FIT:
            return 0.0
        A = np.array(self._X)
        y = np.array(self._y)
        # ridge regression on a degree-2 feature expansion
        def expand(M):
            return np.concatenate([M, M**2, np.ones((len(M), 1))], axis=1)
        Ae, Xe = expand(A), expand(np.array([self._featurize(exp)]))
        w = np.linalg.solve(Ae.T @ Ae + 1e-3 * np.eye(Ae.shape[1]), Ae.T @ y)
        return float((Xe @ w)[0])

    def next_batch(self, sample_size=1):
        ranked = sorted(self.all_exps, key=self._predict,
                        reverse=(self.mode == "max"))
        batch = ranked[:sample_size]
        for b in batch:
            self.all_exps.remove(b)
        return batch

    def update(self, exps, results):
        super().update(exps, results)
        for exp, res in zip(exps, results):
            if res is not None and res.get(self.metric) is not None:
                self._X.append(self._featurize(exp))
                self._y.append(res[self.metric])
