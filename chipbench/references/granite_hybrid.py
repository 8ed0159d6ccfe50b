"""Plain reference of one FedGS round loop with the configuration's hybrid
Mamba-2/attention language model (the keys of the published granite-4.0-h
``config.json``).

Straightforward ``jax.numpy`` at float32 under
``jax.default_matmul_precision("highest")``; the control runs it in
bfloat16.  It imports nothing of the program: the model, its
initialisation from the cell's seed, the data-to-token map, the local SGD
steps and their minibatch draws, the 3DG build, the FedGS solve, FedAvg
and the eval are written out here from the published description and the
configuration, and the round loop is ``fedgs_cnn.py``'s.  The random
streams are the cell's: the same keys give the same weights, minibatches
and sets as the program's cell of that seed.

The model, layer by layer (r the residual multiplier, one period of
``layer_types``):

  x = embed[ids] * embedding_multiplier
  per layer:  x += r * mixer(rms_norm(x));  x += r * mlp(rms_norm(x))
  logits = rms_norm(x) @ embed.T / logits_scaling

* attention: GQA, no positional encoding, scores * attention_multiplier,
  causal softmax;
* Mamba-2: z, x, B, C, dt = projections of the input; (x, B, C) through a
  depthwise causal conv of ``mamba_d_conv`` with bias and SiLU;
  dt = softplus(dt + dt_bias); the SSD in its quadratic (dual) form,
  y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s x_s + D x_t,
  per head (A = -exp(A_log)); then rms_norm(y * silu(z)) * w @ out_proj;
* mlp: SwiGLU, (silu(x W_gate) * (x W_in)) W_out.

Each layer is recomputed in the backward pass (``jax.checkpoint``) and the
SSD and attention run over groups of heads, so a training step of the
configuration's widths fits one chip beside four copies of the
parameters.  ``replay`` follows one cell for the first ``k`` rounds of a
sweep on the program's sampled sets, training the round's clients one
after another.  ``fault`` plants a fault in the reference put in the
program's place: ``"half"`` leaves half of each round's clients out of the
aggregation, ``"alter"`` alters one sampled set (``fedgs_cnn``'s), and
``FAULTS`` break the model: the attention layer skipped, the gated norm
dropped, the residual multiplier at 1.0.
"""
from __future__ import annotations

import os
import sys
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import fedgs_cnn as fl  # noqa: E402  (the round loop's shared pieces)

HIGHEST = jax.lax.Precision.HIGHEST
HEAD_GROUPS = 4            # heads are processed in this many groups
EVAL_BLOCK = 8             # held-out documents per eval forward
FAULTS = ("skip_attention", "no_gated_norm", "residual_1")
build_h = fl.build_h


# ------------------------------------------------------------------ model
def dims(cfg: dict) -> dict:
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    d_in = cfg["mamba_expand"] * d
    return {"d": d, "hq": heads, "hkv": cfg["num_key_value_heads"],
            "dh": d // heads, "ff": cfg["intermediate_size"],
            "v": cfg["vocab_size"], "d_in": d_in, "n": cfg["mamba_d_state"],
            "mh": cfg["mamba_n_heads"], "mp": cfg["mamba_d_head"],
            "k": cfg["mamba_d_conv"], "conv": d_in + 2 * cfg["mamba_d_state"]}


def _matrix(key, fan_in, fan_out):
    """Truncated normal on [-3, 3] over sqrt(fan_in)."""
    return jax.random.truncated_normal(key, -3, 3, (fan_in, fan_out),
                                       jnp.float32) * (1.0 / np.sqrt(fan_in))


def init_params(key, cfg: dict, dtype=jnp.float32) -> dict:
    """The zoo's init, one key per layer: key 0 of split(key, L + 1) the
    embedding (normal, 0.02), key i+1 layer i, split into the mixer's key
    and the mlp's.  Returned flat: ``layers.<i>.<part>.<weight>``."""
    g = dims(cfg)
    d = g["d"]
    ks = jax.random.split(key, len(cfg["layer_types"]) + 1)
    p = {"embed": jax.random.normal(ks[0], (g["v"], d), jnp.float32) * 0.02,
         "final_norm": jnp.ones((d,))}
    for i, (kind, k) in enumerate(zip(cfg["layer_types"], ks[1:])):
        km, kf = jax.random.split(k)
        pre = f"layers.{i}.{kind}."
        if kind == "attention":
            q = jax.random.split(km, 4)
            p[pre + "wq"] = _matrix(q[0], d, g["hq"] * g["dh"])
            p[pre + "wk"] = _matrix(q[1], d, g["hkv"] * g["dh"])
            p[pre + "wv"] = _matrix(q[2], d, g["hkv"] * g["dh"])
            p[pre + "wo"] = _matrix(q[3], g["hq"] * g["dh"], d)
        else:
            q = jax.random.split(km, 8)
            u = jax.random.uniform(q[5], (g["mh"],), jnp.float32,
                                   np.log(1e-3), np.log(1e-1))
            p[pre + "w_z"] = _matrix(q[0], d, g["d_in"])
            p[pre + "w_x"] = _matrix(q[1], d, g["d_in"])
            p[pre + "w_B"] = _matrix(q[2], d, g["n"])
            p[pre + "w_C"] = _matrix(q[3], d, g["n"])
            p[pre + "w_dt"] = _matrix(q[4], d, g["mh"])
            p[pre + "dt_bias"] = jnp.log(jnp.expm1(jnp.exp(u)))
            p[pre + "A_log"] = jnp.log(jnp.linspace(1.0, 16.0, g["mh"]))
            p[pre + "D"] = jnp.ones((g["mh"],))
            p[pre + "conv_w"] = jax.random.normal(
                q[6], (g["k"], g["conv"]), jnp.float32) * 0.1
            p[pre + "conv_b"] = jnp.zeros((g["conv"],))
            p[pre + "gate_norm"] = jnp.ones((g["d_in"],))
            p[pre + "w_out"] = _matrix(q[7], g["d_in"], d)
        p[pre + "norm"] = jnp.ones((d,))
        f = jax.random.split(kf, 3)
        p[f"layers.{i}.mlp.w_in"] = _matrix(f[0], d, g["ff"])
        p[f"layers.{i}.mlp.w_out"] = _matrix(f[1], g["ff"], d)
        p[f"layers.{i}.mlp.w_gate"] = _matrix(f[2], d, g["ff"])
        p[f"layers.{i}.mlp.norm"] = jnp.ones((d,))
    return {k: v.astype(dtype) for k, v in p.items()}


def token_ids(x, vocab: int):
    """floor(Phi(x) * V), clipped to [0, V-1]: each data value a token."""
    u = jax.scipy.special.ndtr(x.astype(jnp.float32))
    return jnp.clip(jnp.floor(u * vocab), 0, vocab - 1).astype(jnp.int32)


def rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def attention(p, h, g, scale):
    """GQA without positions; the (query-head group) loop keeps one
    group's (B, heads, S, S) scores live."""
    b, s, _ = h.shape
    q = (h @ p["wq"]).reshape(b, s, g["hkv"], g["hq"] // g["hkv"], g["dh"])
    k = (h @ p["wk"]).reshape(b, s, g["hkv"], g["dh"])
    v = (h @ p["wv"]).reshape(b, s, g["hkv"], g["dh"])
    causal = jnp.tril(jnp.ones((s, s), bool))

    def kv_group(args):
        qg, kg, vg = args                       # (b,s,r,dh), (b,s,dh) x2
        sc = jnp.einsum("bqrd,bkd->brqk", qg, kg).astype(jnp.float32)
        sc = jnp.where(causal, sc * scale, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1).astype(h.dtype)
        return jnp.einsum("brqk,bkd->bqrd", pr, vg)

    o = jax.lax.map(jax.checkpoint(kv_group), (
        q.transpose(2, 0, 1, 3, 4), k.transpose(2, 0, 1, 3),
        v.transpose(2, 0, 1, 3)))               # (hkv, b, s, r, dh)
    o = o.transpose(1, 2, 0, 3, 4).reshape(b, s, g["hq"] * g["dh"])
    return o @ p["wo"]


def ssd_dual(xh, dt, a, bm, cm):
    """The SSD as one masked (S, S) product per head.  xh (b,s,h,p),
    dt (b,s,h), a (h,) negative, bm / cm (b,s,n) -> (b,s,h,p) float32."""
    s = xh.shape[1]
    cs = jnp.cumsum(dt * a, axis=1)                       # (b,s,h)
    seg = cs[:, :, None, :] - cs[:, None, :, :]           # (b,t,s,h)
    causal = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("btn,bsn->bts", cm, bm)
    return jnp.einsum("btsh,bts,bsh,bshp->bthp", decay, cb, dt, xh)


def mamba(p, h, g, eps, gated_norm):
    b, s, _ = h.shape
    z = h @ p["w_z"]
    xbc = jnp.concatenate([h @ p["w_x"], h @ p["w_B"], h @ p["w_C"]], -1)
    pad = jnp.concatenate([jnp.zeros((b, g["k"] - 1, g["conv"]), h.dtype),
                           xbc], axis=1)
    conv = sum(pad[:, i:i + s] * p["conv_w"][i] for i in range(g["k"]))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs, bm, cm = jnp.split(xbc, [g["d_in"], g["d_in"] + g["n"]], axis=-1)
    dt = jax.nn.softplus((h @ p["w_dt"]).astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    xh = xs.reshape(b, s, g["mh"], g["mp"]).astype(jnp.float32)
    hg = g["mh"] // HEAD_GROUPS

    def heads(args):
        xg, dg, ag = args
        return ssd_dual(xg, dg, ag, bm.astype(jnp.float32),
                        cm.astype(jnp.float32))
    y = jax.lax.map(jax.checkpoint(heads), (
        xh.reshape(b, s, HEAD_GROUPS, hg, g["mp"]).transpose(2, 0, 1, 3, 4),
        dt.reshape(b, s, HEAD_GROUPS, hg).transpose(2, 0, 1, 3),
        a.reshape(HEAD_GROUPS, hg)))
    y = y.transpose(1, 2, 0, 3, 4).reshape(b, s, g["mh"], g["mp"])
    y = y + xh * p["D"].astype(jnp.float32)[:, None]
    y = y.reshape(b, s, g["d_in"]).astype(h.dtype) * jax.nn.silu(z)
    y = jnp.where(gated_norm, rms(y, p["gate_norm"], eps), y)
    return y @ p["w_out"]


def mlp(p, h):
    return (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_in"])) @ p["w_out"]


def knobs_of(cfg: dict, fault=None) -> dict:
    """The model's fault knobs, handed to the jitted pieces as values (so
    one compile serves the model and its planted faults): the residual
    multiplier, the attention branch's factor, the gated norm on or off."""
    return {"r": np.float32(1.0 if fault == "residual_1"
                            else cfg["residual_multiplier"]),
            "attn": np.float32(fault != "skip_attention"),
            "gnorm": np.bool_(fault != "no_gated_norm")}


def layer_fn(cfg: dict, kind: str, kn: dict):
    """x -> x + r mixer(norm x) -> + r mlp(norm x), one layer's params."""
    g, eps = dims(cfg), cfg["rms_norm_eps"]

    def fn(lp, x):
        m = lp[kind]
        r = kn["r"].astype(x.dtype)
        h = rms(x, m["norm"], eps)
        if kind == "attention":
            out = kn["attn"].astype(x.dtype) * attention(
                m, h, g, cfg["attention_multiplier"])
        else:
            out = mamba(m, h, g, eps, kn["gnorm"])
        x = x + out * r
        return x + mlp(lp["mlp"], rms(x, lp["mlp"]["norm"], eps)) * r
    return fn


def nested_layers(p: dict, n: int) -> list:
    out = [{} for _ in range(n)]
    for k, v in p.items():
        if k.startswith("layers."):
            _, i, part, w = k.split(".")
            out[int(i)].setdefault(part, {})[w] = v
    return out


def hidden(cfg: dict, p: dict, ids, kn: dict):
    """The last hidden state after the final norm, each layer recomputed
    in the backward pass."""
    x = p["embed"][ids] * cfg["embedding_multiplier"]
    for kind, lp in zip(cfg["layer_types"],
                        nested_layers(p, len(cfg["layer_types"]))):
        x = jax.checkpoint(layer_fn(cfg, kind, kn))(lp, x)
    return rms(x, p["final_norm"], cfg["rms_norm_eps"])


def logits_of(cfg: dict, p: dict, h):
    return (h @ p["embed"].T).astype(jnp.float32) / cfg["logits_scaling"]


def doc_loss(cfg: dict, p: dict, x, kn: dict):
    """Mean next-token cross entropy of the documents ``x`` (data rows)."""
    ids = token_ids(x, cfg["vocab_size"])
    lg = logits_of(cfg, p, hidden(cfg, p, ids[:, :-1], kn))
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None], -1))


# ---------------------------------------------------------------- replay
class Reference:
    """One configuration's data on the device, and jitted round pieces."""

    def __init__(self, cfg: dict, traffic: dict, ds, dtype=jnp.float32,
                 fault=None):
        self.cfg, self.traffic, self.dtype = cfg, traffic, dtype
        self.m, self.steps, self.batch = cfg["m"], cfg["local_steps"], \
            cfg["batch_size"]
        self.n = ds.n_clients
        self.sizes = np.asarray(ds.sizes)
        self.xs = jnp.asarray(ds.x)
        self.sz = jnp.asarray(ds.sizes, jnp.int32)
        self.xv = jnp.asarray(ds.x_val)
        self.knobs = knobs_of(cfg, fault)
        hp = partial(jax.default_matmul_precision, "highest")
        self.init = jax.jit(lambda k: init_params(k, cfg, dtype))
        steps, batch = self.steps, self.batch

        def sgd(params, x, n_k, lr, key, kn):
            """``steps`` SGD steps on minibatches drawn with replacement."""
            def step(q, k):
                idx = jax.random.randint(k, (batch,), 0, jnp.maximum(n_k, 1))
                gr = jax.grad(lambda w: doc_loss(cfg, w, x[idx], kn))(q)
                return {n: q[n] - lr.astype(q[n].dtype) * gr[n]
                        for n in q}, None
            return jax.lax.scan(step, params,
                                jax.random.split(key, steps))[0]

        def train(params, xs, sz, k, lr, key, kn):
            with hp():
                return sgd(params, xs[k], sz[k], lr, key, kn)

        def add(acc, local, w):
            return {n: acc[n] + w * local[n].astype(jnp.float32)
                    for n in acc}

        def evaluate(params, x, kn):
            with hp():
                return doc_loss(cfg, params, x, kn).astype(jnp.float32)

        self._train = jax.jit(train)
        self._add = jax.jit(add, donate_argnums=(0,))
        self._eval = jax.jit(evaluate)
        g = traffic["graph"]
        assert g["kind"] == "oracle_static", \
            "the hybrid LM's reference replays a static 3DG"

    def lr(self, t: int):
        tr = self.cfg
        return jnp.float32(np.float32(tr["lr"] * tr["lr_decay"] ** t))

    def evaluate(self, params) -> float:
        xv = self.xv
        tot = 0.0
        for b0 in range(0, len(xv), EVAL_BLOCK):
            blk = xv[b0:b0 + EVAL_BLOCK]
            tot += float(self._eval(params, blk, self.knobs)) * len(blk)
        return tot / len(xv)

    def fedavg(self, params, order, w):
        """Eq. 18, client by client: each client's params trained from
        ``params`` and added with its normalised weight."""
        acc = {n: jnp.zeros(a.shape, jnp.float32) for n, a in params.items()}
        wn = w / w.sum()
        for j, (k, key) in enumerate(order):
            local = self._train(params, self.xs, self.sz, int(k), self._lr_t,
                                key, self.knobs)
            acc = self._add(acc, local, jnp.float32(wn[j]))
            del local
        return {n: a.astype(self.dtype) for n, a in acc.items()}

    def replay(self, spec, k: int, sets=None, h_static=None, fault=None):
        """Follow one cell for rounds 0..k-1 on the program's ``sets``
        ((k, m) training slots and valid flags), or on this reference's own
        decisions without them.  Returns the params before and after, the
        per-round objective gaps of the used sets and the eval losses."""
        tr, n, m = self.cfg, self.n, self.m
        samp = self.traffic["sampler"]
        params = self.init(jax.random.PRNGKey(spec["seed"]))
        params0 = jax.device_get(params)
        h = np.asarray(h_static, np.float64)
        counts = np.zeros(n)
        gaps, losses, used = [], {}, []
        for t in range(k):
            avail = np.asarray(spec["masks"][t], bool)
            q = fl.eq16_q(h, counts, samp["alpha"], m)
            best = fl.solve(q, avail, m, samp["max_sweeps"])
            if sets is None:
                order, valid = fl.gather_order(best, m)
                if fault == "alter" and t == 1:
                    order, valid = fl._alter(order, valid, avail, counts)
            else:
                order = np.asarray(sets[0][t])
                valid = np.asarray(sets[1][t], bool)
            s = np.zeros(n, bool)
            s[order[valid]] = True
            gaps.append(fl._set_gap(q, best, s, order, valid, avail, m))
            used.append((order, valid))
            key = jax.random.fold_in(jax.random.PRNGKey(spec["seed"]), t)
            _, sub = jax.random.split(key)
            keys = jax.random.split(sub, m)
            w_valid = valid.copy()
            if fault == "half":
                w_valid[m // 2:] = False
            w = self.sizes[order].astype(np.float32) * w_valid
            if w.sum() > 0:
                self._lr_t = self.lr(t)
                picked = [(order[j], keys[j]) for j in range(m) if w[j] > 0]
                params = self.fedavg(params, picked, w[w > 0])
            counts = counts + s
            if t % tr["eval_every"] == 0 or t == tr["rounds"] - 1:
                losses[t] = self.evaluate(params)
        return {"params": jax.device_get(params), "params0": params0,
                "gaps": gaps, "losses": losses, "sets": used,
                "h": h, "h0": h}
