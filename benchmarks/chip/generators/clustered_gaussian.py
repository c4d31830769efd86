"""Clustered Gaussian vectors, a copy of the generator of
``repro.data.synth``: ``n_clusters`` isotropic clusters whose centres are
``center_scale`` times a standard normal, each point its centre plus
``cluster_std`` times a standard normal; a query moves from its corpus
point by ``query_noise`` times ``cluster_std``."""

import numpy as np


def draw(data: dict, g: np.random.Generator) -> np.ndarray:
    n, dim = int(data["n"]), int(data["dim"])
    centers = data["center_scale"] * g.normal(
        size=(data["n_clusters"], dim)).astype(np.float32)
    assign = g.integers(0, data["n_clusters"], size=n)
    return centers[assign] + data["cluster_std"] * g.normal(
        size=(n, dim)).astype(np.float32)


def query_noise(data: dict) -> float:
    return data["query_noise"] * data["cluster_std"]
