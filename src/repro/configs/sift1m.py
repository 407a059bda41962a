"""sift1m — the paper's own flagship configuration: a δ-EMQG index over a
SIFT-like corpus (n=1M, d=128) served with the error-bounded probing
search.  Build params follow Sec. 7 (L=1000, M=64, I=3); search uses
k ∈ {1, 10, 100} with α sweeps.  Source: ANN-Benchmarks SIFT1M (paper
Sec. 7).

``SERVE_BATCH`` and ``SERVE_ONLINE`` are the batch sizes of the bulk and the
online serve paths; ``SMOKE`` is a reduced copy for CPU smoke tests.
"""

from repro.core import BuildParams, SearchParams

N = 1_000_000
DIM = 128
BUILD = BuildParams(max_degree=64, beam_width=1000, t=64, iters=3,
                    align_degree=True)
SEARCH = SearchParams(k=10, l0=10, l_max=512, alpha=1.2, adaptive=True,
                      max_hops=4096)
SERVE_BATCH = 4096
SERVE_ONLINE = 256

SMOKE = {
    "n": 2000,
    "dim": 32,
    "build": BuildParams(max_degree=16, beam_width=32, t=8, iters=2),
    "search": SearchParams(k=10, l0=10, l_max=64, alpha=1.3,
                           adaptive=True, max_hops=512),
}
