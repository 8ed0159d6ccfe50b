"""The 3DG pipeline — ONE device-native implementation (DESIGN.md §9).

Every layer that builds or normalizes a Data-Distribution-Dependency Graph
(paper §3.2, Eq. 11–13) goes through the composable stages below:

    features U (N, d)
       │  dot_sim / cosine_sim            similarity source (Eq. 11/12)
       ▼
    similarity V (N, N)
       │  minmax01                        Appendix C [0, 1] normalization
       ▼
    normalized similarity Vn
       │  to_adjacency(eps, sigma2)       R_ij = exp(-Vn/σ²) | inf, diag 0
       ▼
    adjacency R (inf = no edge)
       │  apsp(backend="ref"|"pallas")    Floyd–Warshall shortest paths
       ▼
    distance matrix H (inf = disconnected)
       │  cap_and_normalize(scale)        finite cap + [0, 1] scale (Eq. 16 prep)
       ▼
    normalized H — what FedGS's QUBO consumes

All stages are pure jnp and jit/vmap/scan-traceable, so the same code runs
in host numpy wrappers (``core/graph.py``), inside the scan engine's
``lax.scan`` body (``fed/scan_engine.py``), and in the production dry-run
(``launch/fedsim.py``).  ``backend="pallas"`` routes the similarity matmul
and the blocked Floyd–Warshall through the tiled TPU kernels in
``kernels/ops.py`` (whose wrappers pad to tile multiples in-trace);
``backend="ref"`` uses the pure-jnp oracles.  Math is float32 throughout —
the same precision the samplers trace (DESIGN.md assumption log #3/#8).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.kernels.ref import floyd_warshall_ref

BACKENDS = ("ref", "pallas")
# similarity sources: "dot" = U Uᵀ (oracle features), "cosine" = row-normalized
# dot (oracle kind="cosine"), "functional" = max(cos, 0) (Eq. 11/12, the
# dynamic-3DG probe path), "precomputed" = input already is V
SIMILARITIES = ("dot", "cosine", "functional", "precomputed")


@dataclass(frozen=True)
class GraphConfig:
    """Static (compile-time) 3DG build configuration — hashable, so it can be
    closed over by jit programs and used as a cache key."""
    eps: float = 0.1               # edge threshold on normalized similarity
    sigma2: float = 0.01           # paper's σ² in exp(-V/σ²)
    finite_cap_scale: float = 2.0  # disconnected pairs ↦ scale × max finite
    normalize: bool = True         # scale H to [0, 1] (DESIGN.md assumption #1)
    similarity: str = "dot"

    def __post_init__(self):
        if self.similarity not in SIMILARITIES:
            raise ValueError(f"similarity must be one of {SIMILARITIES}, "
                             f"not {self.similarity!r}")


# ------------------------------------------------------------------- stages
def dot_sim(u: jax.Array, *, backend: str = "ref",
            interpret: bool | None = None) -> jax.Array:
    """V = U Uᵀ.  The pallas backend runs the tiled MXU matmul.  The ref
    matmul asks for float32 precision: a TPU's default f32 matmul rounds
    its operands to bf16, and V feeds exp(-V/σ²), which magnifies that
    error far past what the pallas kernel's float32 products show."""
    if backend == "pallas":
        from repro.kernels.ops import pairwise_similarity
        return pairwise_similarity(u, interpret=interpret)
    return jnp.matmul(u, u.T, precision=jax.lax.Precision.HIGHEST)


def cosine_sim(u: jax.Array, *, clamp: bool = True, backend: str = "ref",
               interpret: bool | None = None) -> jax.Array:
    """Row-normalized similarity; ``clamp`` gives Eq. 11/12's max(cos, 0)."""
    un = u / jnp.maximum(jnp.linalg.norm(u, axis=-1, keepdims=True), 1e-12)
    v = dot_sim(un, backend=backend, interpret=interpret)
    return jnp.maximum(v, 0.0) if clamp else v


def minmax01(v: jax.Array) -> jax.Array:
    """Min-max normalize similarities to [0, 1] (paper Appendix C)."""
    lo, hi = jnp.min(v), jnp.max(v)
    return (v - lo) / jnp.maximum(hi - lo, 1e-12)


def to_adjacency(vn: jax.Array, *, eps: float = 0.1,
                 sigma2: float = 0.01) -> jax.Array:
    """Normalized similarity -> 3DG adjacency (inf = no edge, diag 0).

    The diagonal is masked with ``jnp.where(eye, 0, ...)`` — never by
    multiplying with ``1 - eye``, which turns an inf no-edge entry into
    ``inf·0 = NaN`` whenever a row's normalized self-similarity falls
    below eps (the hazard the regression tests pin).
    """
    eye = jnp.eye(vn.shape[-1], dtype=bool)
    r = jnp.where(vn >= eps, jnp.exp(-vn / sigma2), jnp.inf)
    return jnp.where(eye, 0.0, r)


def apsp(r: jax.Array, *, backend: str = "ref",
         interpret: bool | None = None) -> jax.Array:
    """All-pairs shortest paths of the (N, N) adjacency.

    ``ref``: the pure-jnp min-plus closure (kernels/ref.py).
    ``pallas``: the blocked VMEM-tiled kernel (kernels/ops.py), padded
    in-trace to the 128 tile multiple with isolated nodes.
    """
    if backend == "pallas":
        from repro.kernels.ops import floyd_warshall
        return floyd_warshall(r.astype(jnp.float32), interpret=interpret)
    if backend != "ref":
        raise ValueError(f"backend must be one of {BACKENDS}, not {backend!r}")
    return floyd_warshall_ref(r.astype(jnp.float32))


def cap_and_normalize(h: jax.Array, *, scale: float = 2.0,
                      normalize: bool = True) -> jax.Array:
    """Replace inf distances (disconnected pairs) with scale × max finite
    distance so the QUBO objective stays finite while still strongly
    preferring disconnected (= maximally dissimilar) pairs; then optionally
    scale to [0, 1] so alpha trades graph dispersion against count balance
    on comparable scales (DESIGN.md assumption log #1)."""
    finite = jnp.isfinite(h)
    mx = jnp.max(jnp.where(finite, h, -jnp.inf))
    cap = scale * jnp.where(jnp.isfinite(mx), mx, 1.0)
    eye = jnp.eye(h.shape[-1], dtype=bool)
    out = jnp.where(eye, 0.0, jnp.where(finite, h, cap))
    if normalize:
        # divide by the true max, however tiny (σ² = 0.01 puts edge weights
        # near 1e-18) — flooring the denominator would leave H ≈ 0 and
        # silently reduce FedGS to count balancing; all-zero H passes through
        hmax = jnp.max(out)
        out = out / jnp.where(hmax > 0, hmax, 1.0)
    return out


# ----------------------------------------------------------------- pipeline
def _similarity(u_or_v: jax.Array, cfg: GraphConfig, *, backend: str,
                interpret: bool | None) -> jax.Array:
    if cfg.similarity == "precomputed":
        return u_or_v
    if cfg.similarity == "dot":
        return dot_sim(u_or_v, backend=backend, interpret=interpret)
    clamp = cfg.similarity == "functional"
    return cosine_sim(u_or_v, clamp=clamp, backend=backend, interpret=interpret)


def build_3dg(u_or_v: jax.Array, cfg: GraphConfig = GraphConfig(), *,
              backend: str = "ref", interpret: bool | None = None):
    """Features (N, d) — or raw similarity (N, N) with
    ``similarity="precomputed"`` — to ``(Vn, R, H_raw)``: the normalized
    similarity, the adjacency, and the *uncapped* shortest-path matrix
    (inf = disconnected)."""
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, not {backend!r}")
    v = _similarity(u_or_v.astype(jnp.float32), cfg, backend=backend,
                    interpret=interpret)
    vn = minmax01(v)
    if backend == "pallas":
        # fused minmax -> threshold -> exp epilogue; lo/hi come from the raw
        # unpadded V, so the result matches the ref stages exactly
        from repro.kernels.ops import similarity_to_adjacency
        r = similarity_to_adjacency(v, eps=cfg.eps, sigma2=cfg.sigma2,
                                    interpret=interpret)
    else:
        r = to_adjacency(vn, eps=cfg.eps, sigma2=cfg.sigma2)
    h = apsp(r, backend=backend, interpret=interpret)
    return vn, r, h


def build_h(u_or_v: jax.Array, cfg: GraphConfig = GraphConfig(), *,
            backend: str = "ref", interpret: bool | None = None) -> jax.Array:
    """The one-call 3DG constructor: features (or similarity) -> finite,
    [0, 1]-normalized H, ready for ``fedgs_select``.  Traceable under
    jit / vmap / lax.scan on both backends.

    On ``backend="pallas"`` with a feature-based similarity the whole
    build routes through the fused megakernel pipeline
    (``kernels/ops.build_3dg_fused``): similarity, min-max stats, and the
    adjacency epilogue run tile-resident in ONE Pallas grid that feeds the
    blocked Floyd–Warshall at a shared padded size — V never exists in
    HBM and R round-trips it exactly once.  Bit-identical to the staged
    pallas stages (tests/test_kernels.py); ``similarity="precomputed"``
    (V given, no features) keeps the staged path."""
    if backend == "pallas" and cfg.similarity != "precomputed":
        from repro.kernels.ops import build_3dg_fused
        u = u_or_v.astype(jnp.float32)
        if cfg.similarity in ("cosine", "functional"):
            # same row normalization (and, via clamp, the same max(·, 0))
            # as cosine_sim — applied before the kernel so the fused matmul
            # consumes exactly the ref path's operand
            u = u / jnp.maximum(jnp.linalg.norm(u, axis=-1, keepdims=True),
                                1e-12)
        _, h = build_3dg_fused(u, eps=cfg.eps, sigma2=cfg.sigma2,
                               clamp=cfg.similarity == "functional",
                               interpret=interpret)
    else:
        _, _, h = build_3dg(u_or_v, cfg, backend=backend, interpret=interpret)
    return cap_and_normalize(h, scale=cfg.finite_cap_scale,
                             normalize=cfg.normalize)
