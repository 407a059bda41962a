"""The system under test: a δ-EMQG index (``build_emqg``: Algorithm 4 with
degree alignment, and 1-bit RaBitQ codes under a random rotation drawn from
the build's seed) built from the corpus and served by
``repro.serve.AnnServer``, which runs the probing search (Algorithm 5,
``probing_search``) for an ``EMQGIndex``, on one chip.

Everything is read from the configuration by name.
"""

from __future__ import annotations


def build(corpus, cfg: dict, seed: int, metrics=None):
    import dataclasses

    import jax

    from repro.core import BuildParams, SearchResult, build_emqg

    # The degree alignment that keeps each refined row and the probing
    # engine that keeps the 1/delta bound came with the engine's probe
    # count: a program without it breaks the bound this configuration
    # states, so it is refused before the build, not judged after it.
    if "n_probes" not in {f.name for f in dataclasses.fields(SearchResult)}:
        raise RuntimeError("this program's probing engine predates the one "
                           "that keeps the 1/delta bound (no "
                           "SearchResult.n_probes)")
    quant = cfg["quantization"]
    if (quant["method"], quant["bits_per_dim"]) != ("rabitq", 1):
        raise ValueError(f"build_emqg fits 1-bit RaBitQ codes, not {quant}")
    bp = BuildParams(max_degree=cfg["max_degree"],
                     beam_width=cfg["build_beam_width"], t=cfg["t"],
                     iters=cfg["build_iters"], delta=cfg["delta"],
                     block=cfg["build_block"],
                     align_degree=cfg["align_degree"], seed=seed)
    return build_emqg(corpus, bp, key=jax.random.PRNGKey(seed),
                      metrics=metrics)


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
