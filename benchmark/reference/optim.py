"""Plain reference of the optimizers a configuration may state under
``training.optimizer``, after gradient clipping by global norm
(``clip_norm``) and under the learning-rate schedule (``schedule``):

  ``ngd``    L2 weight decay added to the gradient, Kaldi's online natural
             gradient (Povey et al. 2015, "Parallel training of DNNs with
             natural gradient and parameter averaging", appendix; the
             reference repository's ``ngd_optimizer.py``) per tensor axis,
             heavy-ball momentum: both paper configurations';
  ``sgd``    the same without the preconditioner;
  ``adamw``  Adam's two moments with bias correction, decoupled weight
             decay (Loshchilov & Hutter 2019), hyper-parameters ``b1``,
             ``b2``, ``eps`` under ``training.adamw``.

float32, every product at ``highest`` precision.  Imports nothing of the
program.

The Fisher factors are refreshed on every call while t < 10 and on every
``update_period``-th call after; the reference follows the first few steps
only, so it refuses t >= 10 and always refreshes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
EPS = 1.0e-10
DELTA = 5.0e-4


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


# -- schedules ---------------------------------------------------------------

def learning_rate(training: dict, steps_per_epoch: int, step: int) -> float:
    kind, lr = training["schedule"], float(training["lr"])
    if kind == "constant":
        return lr
    if kind == "multistep":
        epoch = step // steps_per_epoch
        drops = sum(1 for m in training["milestones"] if epoch >= m)
        return lr * float(training["gamma"]) ** drops
    if kind == "onecycle":
        # cosine ramp from peak/25 up to the peak over the first 30% of all
        # steps, cosine decay to peak/25/1e4 over the rest
        total = max(1, int(training["epochs"]) * steps_per_epoch)
        peak = lr * 5.0
        start, end = peak / 25.0, peak / 25.0 / 1.0e4
        turn = int(0.3 * total)
        if step < turn:
            frac = step / turn
            return start + (peak - start) * (1 - math.cos(math.pi * frac)) / 2
        frac = min(1.0, (step - turn) / max(1, total - turn))
        return peak + (end - peak) * (1 - math.cos(math.pi * frac)) / 2
    raise ValueError(f"schedule {kind!r}")


# -- online natural gradient, one axis of one tensor -------------------------

def default_rank(dim: int) -> int:
    return min((dim + 1) // 2, 80)


def well_determined(shape, hp: dict) -> bool:
    """Whether every axis of a tensor that is preconditioned sees at least
    as many rows (the tensor's other elements) as its factors have rank.
    Where it sees fewer (a bias, a norm vector, the classifier's (2048, 10)
    on its long axis, a 1x1 kernel with 64 rows against rank 80) the
    low-rank Fisher estimate is under-determined and the DIRECTION of the
    preconditioned gradient is not reproducible, only its norm."""
    size = math.prod(shape)
    return all(size // dim >= default_rank(dim) for dim in shape
               if 1 < dim <= int(hp["max_dim"]))


def well_determined_leaves(shapes, training: dict) -> list:
    """``well_determined`` a leaf under ``ngd``; every leaf under an
    optimizer that preconditions nothing."""
    if training["optimizer"] != "ngd":
        return [True for _ in shapes]
    return [well_determined(shape, training["ngd"]) for shape in shapes]


def start_factors(dim: int, rank: int, alpha: float):
    """W_0 = sqrt(e) * a fixed near-orthonormal (rank, dim) matrix: scaled
    identities side by side, the first with 1.1 on its diagonal."""
    cols, rest = dim // rank, dim % rank
    k = np.full((rank,), 1.0 / math.sqrt(1.1 * 1.1 + cols - 1))
    k[:rest] = 1.0 / math.sqrt(1.1 * 1.1 + cols)
    blocks = [np.diag(k * 1.1)] + [np.diag(k)] * (cols + 1)
    ortho = np.concatenate(blocks, axis=1)[:, :dim]
    e = 1.0 / (2.0 + (dim + rank) * alpha / dim)
    return (jnp.asarray(math.sqrt(e) * ortho, jnp.float32),
            jnp.full((rank,), EPS, jnp.float32),
            jnp.asarray(EPS, jnp.float32))


def refresh(w, d, rho, x, tr, h, alpha: float, eta: float):
    """F_{t+1} from F_t and this minibatch of directions x (N, dim)."""
    n, dim = x.shape
    rank = w.shape[0]
    eta_n = eta / n
    j = mm(h.T, x)
    l_mat = mm(j, w.T) if n > dim else mm(h.T, h)
    k_mat = mm(j, j.T)
    d_sum = jnp.sum(d)
    beta = rho * (1 + alpha) + alpha * d_sum / dim
    e = 1.0 / (beta / d + 1.0)
    ise = 1.0 / jnp.sqrt(e)
    zs = jnp.maximum(1.0, jnp.trace(k_mat))
    dpr = d + rho
    cross = (eta_n * (1 - eta) / zs) * jnp.outer(ise, ise * dpr)
    z = (k_mat * ((eta_n ** 2) / zs) * jnp.outer(ise, ise)
         + l_mat * (cross + cross.T)
         + jnp.diag(((1 - eta) ** 2 / zs) * dpr * dpr))
    c, u = jnp.linalg.eigh(0.5 * (z + z.T))
    c, u = c[::-1], u[:, ::-1]
    c = jnp.maximum(c, ((rho * (1 - eta)) ** 2) / zs)
    sc = jnp.sqrt(c) * jnp.sqrt(zs)
    rho1 = (eta_n * tr + (1 - eta) * (dim * rho + d_sum)
            - jnp.sum(sc)) / (dim - rank)
    floor = jnp.maximum(EPS, DELTA * jnp.max(sc))
    d1 = jnp.maximum(sc - rho1, floor)
    rho1 = jnp.maximum(rho1, floor)
    beta1 = rho1 * (1 + alpha) + alpha * jnp.sum(d1) / dim
    e1 = 1.0 / (beta1 / d1 + 1.0)
    b = j + (((1 - eta) / eta_n) * dpr)[:, None] * w
    a = u.T * jnp.outer(eta_n * jnp.sqrt(e1) / sc, ise)
    return mm(a, b), d1, rho1


def precondition_matrix(factors, x, first: bool, hp: dict):
    """(new factors, preconditioned x) for one (N, dim) matrix; ``first``
    is the call that starts the factors from this minibatch."""
    alpha, eta = float(hp["alpha"]), float(hp["eta"])
    dim = x.shape[1]
    tr = jnp.sum(x * x)
    if first:
        w, d, rho = start_factors(dim, default_rank(dim), alpha)
        for _ in range(3):          # a cheap power iteration on this batch
            w, d, rho = refresh(w, d, rho, x, tr, mm(x, w.T), alpha, eta)
    else:
        w, d, rho = factors
    h = mm(x, w.T)
    out = x - mm(h, w)
    new = refresh(w, d, rho, x, tr, h, alpha, eta)
    size = jnp.sum(out * out)
    good = jnp.isfinite(size)
    # the preconditioned gradient keeps the Euclidean norm of the raw one
    out = jnp.where(good, out * jnp.sqrt(tr / (size + 1.0e-30)), x)
    new = tuple(jnp.where(good, a, b) for a, b in zip(new, (w, d, rho)))
    return new, out


def precondition(factors: dict, grads: list, first: bool, hp: dict):
    """Axis 0 of every tensor, then axis 1, ...; an axis of size 1 or over
    ``max_dim`` is left alone.  Same-shaped problems of one round are
    stacked and mapped together (one eigendecomposition call for the lot):
    a block of the work, not a change of it."""
    work = [g.astype(jnp.float32) for g in grads]
    new_factors = dict(factors)
    for r in range(max(g.ndim for g in work)):
        groups = {}
        for i, g in enumerate(work):
            if g.ndim > r and 1 < g.shape[r] <= int(hp["max_dim"]):
                groups.setdefault((g.size // g.shape[r], g.shape[r]),
                                  []).append(i)
        for (n, dim), members in groups.items():
            moved = [jnp.moveaxis(work[i], r, -1) for i in members]
            xs = jnp.stack([m.reshape(n, dim) for m in moved])
            key = (r, n, dim)
            if first:
                new, outs = jax.vmap(
                    lambda x: precondition_matrix(None, x, True, hp))(xs)
            else:
                new, outs = jax.vmap(
                    lambda f, x: precondition_matrix(f, x, False, hp))(
                        factors[key], xs)
            new_factors[key] = new
            for slot, i in enumerate(members):
                work[i] = jnp.moveaxis(outs[slot].reshape(moved[slot].shape),
                                       -1, r)
    return new_factors, work


# -- the whole update --------------------------------------------------------

def clipped(grads: list, training: dict) -> list:
    clip = float(training["clip_norm"])
    total = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads))
    return [jnp.where(total < clip, g, g / total * clip) for g in grads]


def _momentum_update(params, grads, state, lr, first, training):
    """``ngd`` and ``sgd``; state = (Fisher factors, momentum trace), the
    factors empty for ``sgd`` and on ``ngd``'s first step."""
    factors, trace = state
    wd = float(training["weight_decay"])
    grads = [g + wd * p for g, p in zip(clipped(grads, training), params)]
    if training["optimizer"] == "ngd":
        factors, grads = precondition(factors, grads, first, training["ngd"])
    m = float(training["momentum"])
    trace = [g + m * b for g, b in zip(grads, trace)]
    params = [p - lr * b for p, b in zip(params, trace)]
    return params, (factors, trace)


def _adamw_update(params, grads, state, lr, first, training):
    """state = (first moments, second moments, steps taken)."""
    mu, nu, count = state
    hp = training["adamw"]
    b1, b2, eps = float(hp["b1"]), float(hp["b2"]), float(hp["eps"])
    wd = float(training["weight_decay"])
    grads = clipped(grads, training)
    mu = [b1 * m + (1 - b1) * g for m, g in zip(mu, grads)]
    nu = [b2 * v + (1 - b2) * g * g for v, g in zip(nu, grads)]
    count = count + 1
    t = count.astype(jnp.float32)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = [p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p)
              for p, m, v in zip(params, mu, nu)]
    return params, (mu, nu, count)


def _zeros(params):
    return [jnp.zeros_like(p) for p in params]


# training.optimizer -> (the state before the first step, one step, where
# the state after the first step keeps the first gradient: the leaves that
# hold it and the number they are to be divided by; the division is the
# reader's, inside its reduction, so that no second tree is made)
_MOMENTUM = (lambda params: ({}, _zeros(params)), _momentum_update,
             lambda state, training: (state[1], 1.0))
OPTIMIZERS = {
    "ngd": _MOMENTUM,
    "sgd": _MOMENTUM,
    "adamw": (lambda params: (_zeros(params), _zeros(params),
                              jnp.zeros((), jnp.int32)), _adamw_update,
              lambda state, training: (
                  state[0], 1 - float(training["adamw"]["b1"]))),
}


def optimizer(training: dict):
    name = training["optimizer"]
    if name not in OPTIMIZERS:
        raise ValueError(f"training.optimizer {name!r} is not in "
                         f"benchmark/reference/optim.py's OPTIMIZERS "
                         f"({sorted(OPTIMIZERS)})")
    return OPTIMIZERS[name]


def start(params: list, training: dict):
    """The optimizer's state before the first step."""
    return optimizer(training)[0](params)


def update(params: list, grads: list, state, lr, first: bool,
           training: dict):
    """One optimizer step on flat lists of leaves; returns (params,
    state).  ``first`` is step 0 (the state as ``start`` made it); ``lr``
    is this step's learning rate."""
    return optimizer(training)[1](params, grads, state, lr, first, training)


def kept_gradient(state, training: dict) -> tuple:
    """(leaves, over): the first gradient as the optimizer kept it is
    ``leaves / over``, from its state after the first step: the momentum
    trace (clipped, decayed, preconditioned at equal norm) over 1, or
    Adam's first moment over 1 - b1 (clipped).  The leaves are the
    state's own, not copies."""
    return optimizer(training)[2](state, training)
