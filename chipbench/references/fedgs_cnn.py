"""Plain reference of one FedGS round loop with the configuration's CNN.

Straightforward ``jax.numpy`` in the precision the configuration states:
float32, with the CNN's convolutions and matmuls at the configuration's
``matmul_precision`` and every other contraction (3DG similarity,
aggregation) at ``highest``; the control runs it in bfloat16.  The Eq. 16
solve is numpy float64.  It imports nothing of the program: the CNN, its
initialisation from the cell's seed, the local SGD steps and their
minibatch draws, the availability draw, the 3DG build, the FedGS solve, the
FedAvg and memory aggregation and the eval are written out here from the
paper and the configuration.  The random streams are the cell's: the same
keys give the same weights, minibatches and availability as the program's
cell of that seed.

``replay`` follows a batch of cells for the first ``k`` rounds of a sweep.
Given the program's sampled sets it trains on them (as a served model's
reference is run over the served tokens) and scores each set against its
own solve of Eq. 16; without them it makes its own decisions.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

NEG = -1e18
SWAP_TOL = 1e-9


# ------------------------------------------------------------------ model
def layer_shapes(cfg: dict, classes: int) -> list:
    """(name, weight shape) of the configuration's CNN in layer order: per
    convolution [kernel, channels] a (k, k, c_in, c_out) kernel, each
    followed by a 2x2 pool; per dense width, and for the logits, a
    (fan_in, fan_out) matrix."""
    h, w, c = cfg["image_shape"]
    out = []
    for i, (k, ch) in enumerate(cfg["conv"]):
        out.append((f"conv{i}", (k, k, c, ch)))
        c = ch
        if cfg["conv_padding"] == "VALID":
            h, w = h - k + 1, w - k + 1
        h, w = h // 2, w // 2
    fan = h * w * c
    for j, width in enumerate(list(cfg["dense"]) + [classes]):
        out.append((f"dense{j}", (fan, width)))
        fan = width
    return out


def init_params(key, shapes: list, dtype):
    """PyTorch's default initialisation of ``Conv2d`` and ``Linear``:
    weight and bias uniform on +-1/sqrt(fan_in), one key per layer in
    layer order, split into the weight's key and the bias's."""
    ks = jax.random.split(key, len(shapes))
    p = {}
    for k, (name, s) in zip(ks, shapes):
        kw, kb = jax.random.split(k)
        lim = 1.0 / np.sqrt(np.prod(s[:-1]))
        p[name] = jax.random.uniform(kw, s, minval=-lim,
                                     maxval=lim).astype(dtype)
        p[name + "_b"] = jax.random.uniform(kb, (s[-1],), minval=-lim,
                                            maxval=lim).astype(dtype)
    return p


HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = {"default": jax.lax.Precision.DEFAULT, "highest": HIGHEST}


def forward(shapes: list, padding: str, precision):
    """logits(p, x): convolution + bias -> ReLU -> 2x2 max pool per
    kernel, then dense + bias -> ReLU per matrix but the last, which gives
    the logits."""
    def logits(p, x):
        a = x.astype(p[shapes[0][0]].dtype)
        for name, s in shapes:
            if len(s) == 4:
                a = jax.lax.conv_general_dilated(
                    a, p[name], (1, 1), padding,
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    precision=precision) + p[name + "_b"]
                a = jax.lax.reduce_window(
                    jax.nn.relu(a), -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                    (1, 2, 2, 1), "VALID")
                continue
            a = jnp.matmul(a.reshape(a.shape[0], -1), p[name],
                           precision=precision) + p[name + "_b"]
            if name != shapes[-1][0]:
                a = jax.nn.relu(a)
        return a
    return logits


def xent(logits, p, x, y):
    logp = jax.nn.log_softmax(logits(p, x))
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def accuracy(logits, p, x, y):
    return jnp.mean(jnp.argmax(logits(p, x), 1) == y)


def local_sgd(logits, params, x, y, n_k, lr, key, steps: int, batch: int):
    """``steps`` SGD steps on minibatches drawn with replacement."""
    def step(p, k):
        idx = jax.random.randint(k, (batch,), 0, jnp.maximum(n_k, 1))
        g = jax.grad(lambda q: xent(logits, q, x[idx], y[idx]))(p)
        return jax.tree_util.tree_map(
            lambda a, b: a - lr.astype(a.dtype) * b, p, g), None
    out, _ = jax.lax.scan(step, params, jax.random.split(key, steps))
    return out


# ------------------------------------------------------------------- 3DG
def shortest_paths(r):
    """Floyd–Warshall over the (N, N) adjacency."""
    def relax(k, d):
        return jnp.minimum(d, d[:, k, None] + d[None, k, :])
    return jax.lax.fori_loop(0, r.shape[0], relax, r)


def build_h(u, *, functional: bool, eps: float, sigma2: float,
            dtype=jnp.float32):
    """Features -> similarity -> [0, 1] -> adjacency exp(-V/sigma2) where
    V >= eps -> shortest paths -> disconnected pairs at twice the largest
    finite distance -> scaled to [0, 1] (paper Eq. 11-13, Appendix C)."""
    u = u.astype(dtype)
    if functional:
        u = u / jnp.maximum(jnp.linalg.norm(u, axis=-1, keepdims=True), 1e-12)
    v = jnp.matmul(u, u.T, precision=HIGHEST)
    if functional:
        v = jnp.maximum(v, 0.0)
    vn = (v - v.min()) / jnp.maximum(v.max() - v.min(), 1e-12)
    eye = jnp.eye(v.shape[0], dtype=bool)
    r = jnp.where(eye, 0.0, jnp.where(vn >= eps, jnp.exp(-vn / sigma2),
                                      jnp.inf))
    d = shortest_paths(r)
    finite = jnp.isfinite(d)
    mx = jnp.max(jnp.where(finite, d, -jnp.inf))
    d = jnp.where(eye, 0.0, jnp.where(finite, d, 2.0 * jnp.where(
        jnp.isfinite(mx), mx, 1.0)))
    top = jnp.max(d)
    return (d / jnp.where(top > 0, top, 1.0)).astype(jnp.float32)


# --------------------------------------------------------------- Eq. 16
def eq16_q(h, counts, alpha: float, m: int) -> np.ndarray:
    """Q = sym(alpha/N H) - diag(z), z = 2 (v - mean v - m/N) + 1."""
    h = np.asarray(h, np.float64)
    n = h.shape[0]
    z = 2.0 * (counts - counts.mean() - m / n) + 1.0
    q = (alpha / n) * h
    q = 0.5 * (q + q.T)
    q[np.diag_indices(n)] -= z
    return q


def objective(q, s) -> float:
    ix = np.flatnonzero(s)
    return float(q[np.ix_(ix, ix)].sum())


def solve(q, avail, m: int, max_sweeps: int) -> np.ndarray:
    """Greedy construction then best-swap local search on max s'Qs,
    |s| = min(m, |A|), s within the available set A."""
    n = q.shape[0]
    s = np.zeros(n, bool)
    r = np.zeros(n)
    diag = np.diag(q)
    for _ in range(m):
        gain = np.where(s | ~avail, NEG, diag + 2.0 * r)
        k = int(np.argmax(gain))
        if gain[k] <= NEG / 2:
            break
        s[k] = True
        r += q[k]
    for _ in range(max_sweeps):
        ins = np.flatnonzero(s)
        outs = ~s & avail
        if not ins.size or not outs.any():
            break
        delta = ((-2.0 * r + diag)[ins, None] + (2.0 * r + diag)[None, :]
                 - 2.0 * q[ins])
        delta[:, ~outs] = NEG
        f = int(np.argmax(delta))
        i, j = ins[f // n], f % n
        if delta.flat[f] <= SWAP_TOL:
            break
        s[i], s[j] = False, True
        r += q[j] - q[i]
    return s


def gather_order(s: np.ndarray, m: int):
    """The m training slots: selected clients ascending, then pad slots
    (not selected, weight 0) ascending."""
    n = len(s)
    order = np.argsort(np.where(s, np.arange(n), n + np.arange(n)),
                       kind="stable")[:m]
    return order, s[order]


# ---------------------------------------------------------------- replay
class Reference:
    """One configuration's data on the device, and jitted round pieces."""

    def __init__(self, cfg: dict, traffic: dict, ds, dtype=jnp.float32):
        self.cfg, self.traffic, self.dtype = cfg, traffic, dtype
        prec = PRECISIONS[cfg["matmul_precision"]]
        self.m, self.steps, self.batch = cfg["m"], cfg["local_steps"], \
            cfg["batch_size"]
        self.n = ds.n_clients
        self.sizes = np.asarray(ds.sizes)
        self.xs = jnp.asarray(ds.x)
        self.ys = jnp.asarray(ds.y)
        self.sz = jnp.asarray(ds.sizes, jnp.int32)
        self.xv = jnp.asarray(ds.x_val)
        self.yv = jnp.asarray(ds.y_val)
        self.shape = tuple(cfg["image_shape"])
        shapes = layer_shapes(cfg, ds.num_classes)
        self.init = jax.jit(lambda k: init_params(k, shapes, dtype))
        logits = forward(shapes, cfg["conv_padding"], prec)
        g = traffic["graph"]
        self.dynamic = g["kind"] == "dynamic"
        if self.dynamic:
            rng = np.random.default_rng(g["probe_seed"])
            flat = np.asarray(ds.x_val, np.float64).reshape(
                len(ds.x_val), -1)
            mu = flat.mean(0)
            cov = np.cov(flat.T) + 1e-4 * np.eye(flat.shape[1])
            probe = rng.multivariate_normal(mu, cov, g["probe_size"])
            self.probe = jnp.asarray(probe.reshape(
                g["probe_size"], *self.shape), jnp.float32)
        steps, batch = self.steps, self.batch

        # the data are arguments, never closed over: a closed-over array
        # is baked into the program as a constant and slows its compile
        def train(params, xs, ys, sz, idx, lr, keys):
            return jax.vmap(lambda x, y, n_k, k: local_sgd(
                logits, params, x, y, n_k, lr, k, steps, batch))(
                xs[idx], ys[idx], sz[idx], keys)
        self._train = jax.jit(train)
        self._embed = jax.jit(lambda stacked, probe: jax.vmap(
            lambda p: jnp.mean(logits(p, probe).astype(jnp.float32),
                               0))(stacked))
        self._evaluate = jax.jit(lambda p, x, y: (
            xent(logits, p, x, y).astype(jnp.float32),
            accuracy(logits, p, x, y)))
        self.graph = jax.jit(lambda u: build_h(
            u, functional=True, eps=g["eps"], sigma2=g["sigma2"],
            dtype=dtype))
        self.fedavg = jax.jit(_fedavg)
        self.memory = jax.jit(_memory)
        self.draw = jax.jit(_draw_avail)

    def train(self, params, idx, lr, keys):
        return self._train(params, self.xs, self.ys, self.sz, idx, lr, keys)

    def embed(self, stacked):
        return self._embed(stacked, self.probe)

    def evaluate(self, params):
        return self._evaluate(params, self.xv, self.yv)

    def lr(self, t: int):
        tr = self.cfg
        return jnp.float32(np.float32(tr["lr"] * tr["lr_decay"] ** t))

    def init_embeddings(self, key, block: int = 512):
        params = self.init(key)
        keys = jax.random.split(key, self.n)
        lr0 = jnp.float32(self.cfg["lr"])
        out = []
        for b0 in range(0, self.n, block):
            idx = jnp.arange(b0, min(b0 + block, self.n))
            out.append(self.embed(self.train(params, idx, lr0, keys[idx])))
        return jnp.concatenate(out)

    def replay(self, spec, k: int, sets=None, h_static=None, fault=None):
        """Follow one cell for rounds 0..k-1.  ``sets`` (k, m) training
        slots and (k, m) valid flags from the program, or None to decide
        here.  Returns the params after round k-1, per-round objective
        gaps of the used sets, the eval losses and the final H."""
        tr = self.cfg
        n, m = self.n, self.m
        agg = self.traffic["aggregator"]
        samp = self.traffic["sampler"]
        params = self.init(jax.random.PRNGKey(spec["seed"]))
        params0 = params
        if self.dynamic:
            emb = self.init_embeddings(jax.random.PRNGKey(spec["seed"] + 778))
            h = self.graph(emb)
        else:
            h = jnp.asarray(h_static)
        h0 = h
        counts = np.zeros(n)
        memory = agg["family"] == "memory"
        if memory:
            mem = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (n,) + a.shape), params)
            tau = np.zeros(n, np.float32)
        gaps, losses, used = [], {}, []
        table = np.asarray(spec["table"], np.float32)
        for t in range(k):
            if spec.get("masks") is not None:
                avail = np.asarray(spec["masks"][t], bool)
            else:
                avail = np.asarray(self.draw(
                    jnp.asarray(table[t % table.shape[0]]),
                    jax.random.fold_in(jax.random.PRNGKey(
                        spec["avail_seed"]), t)))
            q = eq16_q(np.asarray(h, np.float64), counts, samp["alpha"], m)
            best = solve(q, avail, m, samp["max_sweeps"])
            if sets is None:
                order, valid = gather_order(best, m)
                if fault == "alter" and t == 1:
                    order, valid = _alter(order, valid, avail, counts)
            else:
                order = np.asarray(sets[0][t])
                valid = np.asarray(sets[1][t], bool)
            s = np.zeros(n, bool)
            s[order[valid]] = True
            gaps.append(_set_gap(q, best, s, order, valid, avail, m))
            used.append((order, valid))
            key = jax.random.fold_in(jax.random.PRNGKey(spec["seed"]), t)
            _, sub = jax.random.split(key)
            keys = jax.random.split(sub, m)
            local = self.train(params, jnp.asarray(order), self.lr(t), keys)
            w_valid = valid.copy()
            if fault == "half":
                w_valid[m // 2:] = False
            if memory:
                tau = np.where(s, np.float32(t), tau)
                age = np.maximum(np.float32(t) - tau, 0.0)
                wm = self.sizes.astype(np.float32) * np.float32(
                    agg["gamma"]) ** age
                mem, params = self.memory(
                    mem, local, jnp.asarray(order), jnp.asarray(w_valid),
                    jnp.asarray(wm / max(wm.sum(), 1e-12), jnp.float32))
            else:
                w = self.sizes[order].astype(np.float32) * w_valid
                if w.sum() > 0:
                    params = self.fedavg(local, jnp.asarray(
                        w / w.sum(), jnp.float32))
            counts = counts + s
            if self.dynamic:
                e = self.embed(local)
                oj = jnp.asarray(order)
                emb = emb.at[oj].set(jnp.where(jnp.asarray(valid)[:, None],
                                               e, emb[oj]))
                if (t + 1) % self.traffic["graph"]["refresh_every"] == 0:
                    h = self.graph(emb)
            if t % tr["eval_every"] == 0 or t == tr["rounds"] - 1:
                losses[t] = float(self.evaluate(params)[0])
        return {"params": jax.device_get(params),
                "params0": jax.device_get(params0),
                "gaps": gaps, "losses": losses, "sets": used,
                "h": np.asarray(h), "h0": np.asarray(h0)}


def _fedavg(local, w):
    """Eq. 18: the size-weighted mean of the clients' models."""
    return jax.tree_util.tree_map(
        lambda a: jnp.tensordot(w.astype(a.dtype), a, axes=(0, 0),
                                precision=HIGHEST), local)


def _memory(mem, local, order, valid, wn):
    """Memory rectification: the used clients' models replace their rows,
    then the new model is the staleness-discounted, size-weighted mean
    over every client's row."""
    mem = jax.tree_util.tree_map(
        lambda a, l: a.at[order].set(jnp.where(
            valid.reshape((-1,) + (1,) * (l.ndim - 1)), l, a[order])),
        mem, local)
    return mem, jax.tree_util.tree_map(
        lambda a: jnp.tensordot(wn.astype(a.dtype), a, axes=(0, 0),
                                precision=HIGHEST), mem)


def _draw_avail(p, key):
    """Bernoulli(p) on ``key``; if nobody is on, one client drawn on
    ``fold_in(key, 1)`` is turned on."""
    n = p.shape[0]
    avail = jax.random.uniform(key, (n,)) < p
    forced = jax.random.randint(jax.random.fold_in(key, 1), (), 0, n)
    return avail | ((jnp.arange(n) == forced) & ~avail.any())


def _set_gap(q, best, s, order, valid, avail, m) -> float:
    """How far the used set's Eq. 16 objective lies below this solve's
    (inf for a set that breaks the rules: a client repeated or not
    available, or the wrong number of clients)."""
    chosen = order[valid]
    if (len(np.unique(chosen)) != len(chosen)
            or not np.all(avail[chosen])
            or len(chosen) != min(m, int(avail.sum()))):
        return float("inf")
    return max(0.0, objective(q, best) - objective(q, s))


def _alter(order, valid, avail, counts):
    """Replace the lowest selected client by the available client left
    out that has taken part most often (a planted fault: an answer that
    breaks Eq. 16's count balance)."""
    chosen = order[valid]
    spare = np.flatnonzero(avail & ~np.isin(np.arange(len(avail)), chosen))
    if not spare.size:
        return order, valid
    s = np.zeros(len(avail), bool)
    s[chosen] = True
    s[chosen.min()] = False
    s[spare[np.argmax(counts[spare])]] = True
    return gather_order(s, len(order))
