"""Synthetic federated logistic-regression data from a seed.

A configuration names a published dataset shape (d with the intercept,
clients, samples per client).  The LIBSVM files cannot be fetched, so a
stand-in dataset of exactly clients x samples rows is drawn once, from the
configuration's own ``dataset_seed``, as the one fixed file a deployment
holds:

* features: standard normal, each kept with probability ``density`` (the
  rest zero), scaled by max(1, sqrt(density * (d - 1)) / 2);
* labels: +1 with probability sigmoid(a . x* + noise * N(0, 1)) for a
  planted x* ~ N(0, I / (d - 1)), else -1;
* the intercept column of ones is appended and each row is multiplied by
  its label (the form both the program and the reference consume).

A run's ``--seed`` then shuffles the samples uniformly at random and cuts
them into clients, as the paper's pipeline does.  Every seed so poses the
same global objective split differently, and a solve's round count does
not swing with the seed's draw of a new dataset.

The draws follow the generator the paper's authors ship as an optional
synthetic problem generator (arXiv:2410.08760, Appendix L.5) in the form the
repository's own data module used when this benchmark was written.
"""

from __future__ import annotations

import numpy as np


def make_problem(config: dict, seed: int) -> np.ndarray:
    """z: (n_clients, n_i, d) float64, label-absorbed, split by ``seed``."""
    d, n, n_i = config["d"], config["n_clients"], config["n_i"]
    density = config["data"]["density"]
    noise = config["data"]["label_noise"]
    rng = np.random.default_rng(config["data"]["dataset_seed"])
    d_raw = d - 1
    n_samples = n * n_i
    x = rng.standard_normal((n_samples, d_raw))
    x = np.where(rng.random((n_samples, d_raw)) < density, x, 0.0)
    x /= max(1.0, np.sqrt(density * d_raw) / 2.0)
    x_star = rng.standard_normal(d_raw) / np.sqrt(d_raw)
    logits = x @ x_star + noise * rng.standard_normal(n_samples)
    y = np.where(rng.random(n_samples) < 1.0 / (1.0 + np.exp(-logits)), 1.0, -1.0)
    a = np.concatenate([x, np.ones((n_samples, 1))], axis=1)
    perm = np.random.default_rng(seed).permutation(n_samples)
    z = a[perm] * y[perm][:, None]
    return z.reshape(n, n_i, d)
