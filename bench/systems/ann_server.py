"""The system under test: a δ-EMG graph index (``build_approx``, Algorithm
4 without degree alignment) built from the corpus and served by
``repro.serve.AnnServer`` over the exact engine ``search`` on one chip.

Everything is read from the configuration by name.
"""

from __future__ import annotations


def build(corpus, cfg: dict, seed: int, metrics=None):
    from repro.core import BuildParams, build_approx

    bp = BuildParams(max_degree=cfg["max_degree"],
                     beam_width=cfg["build_beam_width"], t=cfg["t"],
                     iters=cfg["build_iters"], delta=cfg["delta"],
                     block=cfg["build_block"], align_degree=False, seed=seed)
    return build_approx(corpus, bp, metrics=metrics)


def server(index, cfg: dict, max_batch: int, buckets, metrics=None,
           tracer=None):
    from repro.core import SearchParams
    from repro.serve import AnnServer

    params = SearchParams(k=cfg["k"], l0=cfg["l0"], l_max=cfg["l_max"],
                          alpha=cfg["alpha"], adaptive=cfg["adaptive"],
                          max_hops=cfg["max_hops"],
                          beam_width=cfg["beam_width"])
    return AnnServer(index, params, max_batch=max_batch,
                     buckets=tuple(buckets), metrics=metrics, tracer=tracer)
