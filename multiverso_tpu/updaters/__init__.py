"""Server-side updaters as pure JAX functions.

TPU-native re-design of the reference updater module
(ref: include/multiverso/updater/updater.h:113-132, src/updater/updater.cpp:38-46
and the concrete sgd/momentum/adagrad headers). In the reference an updater is a
stateful C++ object applied by the server actor, OpenMP-parallel over the shard.
Here an updater is a pair of pure functions:

* ``init_state(shape, dtype)``  -> pytree of state arrays (same sharding as data)
* ``apply(data, state, delta, opt)`` -> (new_data, new_state)

applied inside a jitted, donated update whose arrays are device-sharded over
the table mesh axis — XLA parallelizes element-wise work across all chips the
way OpenMP parallelized it across cores (ref src/updater/updater.cpp:14-22).

Semantics parity notes (signs follow the reference):
* default:      data += delta                       (plain Add aggregation)
* sgd:          data -= delta                       (lr pre-multiplied by worker,
                                                     ref sgd_updater.h:14-19)
* momentum_sgd: smooth = m*smooth + (1-m)*delta; data -= smooth
                                                    (ref momentum_updater.h:17-25)
* adagrad:      G += delta**2 / lr**2 ; data -= delta * rho / (sqrt(G)+eps)
                The reference keeps *per-worker* G buffers
                (ref adagrad_updater.h:19); we default to one shared buffer
                (idiomatic, W× less memory) with ``per_worker=True`` opt-in.
* adam:         first-class here (BASELINE config 5 calls for it; the reference
                never shipped one).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp


def _rule(apply):
    """Name a rule's operations ``mv.rowapply.rule`` in a device trace
    (metadata only: the compiled program is the same). A scope per call:
    one shared ``named_scope`` object is not safe across tracing threads."""
    @functools.wraps(apply)
    def scoped(self, data, state, delta, opt):
        with jax.named_scope("mv.rowapply.rule"):
            return apply(self, data, state, delta, opt)
    return scoped


class AddOption(NamedTuple):
    """Wire-parity hyperparameter bundle (ref updater.h:10-70 AddOption)."""
    worker_id: int = 0
    momentum: float = 0.0
    learning_rate: float = 0.1
    rho: float = 0.1
    lam: float = 0.0  # "lambda" in the reference


class Updater:
    """Base updater: plain accumulation (ref updater.cpp:14-22 default)."""

    name = "default"

    def __init__(self, num_workers: int = 1):
        self.num_workers = num_workers

    def init_state(self, shape, dtype) -> Any:
        return ()

    @_rule
    def apply(self, data: jax.Array, state: Any, delta: jax.Array,
              opt: AddOption) -> Tuple[jax.Array, Any]:
        return data + delta, state


class SGDUpdater(Updater):
    name = "sgd"

    @_rule
    def apply(self, data, state, delta, opt):
        return data - delta, state


class MomentumUpdater(Updater):
    name = "momentum_sgd"

    def init_state(self, shape, dtype):
        return {"smooth": jnp.zeros(shape, dtype)}

    @_rule
    def apply(self, data, state, delta, opt):
        m = jnp.asarray(opt.momentum, data.dtype)
        smooth = m * state["smooth"] + (1.0 - m) * delta
        return data - smooth, {"smooth": smooth}


class AdaGradUpdater(Updater):
    name = "adagrad"

    def __init__(self, num_workers: int = 1, per_worker: bool = False,
                 eps: float = 1e-10):
        super().__init__(num_workers)
        self.per_worker = per_worker
        self.eps = eps

    def init_state(self, shape, dtype):
        if self.per_worker:
            return {"g_sqr": jnp.zeros((self.num_workers,) + tuple(shape), dtype)}
        return {"g_sqr": jnp.zeros(shape, dtype)}

    @_rule
    def apply(self, data, state, delta, opt):
        lr = jnp.asarray(opt.learning_rate, data.dtype)
        rho = jnp.asarray(opt.rho, data.dtype)
        g2 = jnp.square(delta) / jnp.square(lr)
        if self.per_worker:
            wid = jnp.asarray(opt.worker_id, jnp.int32)
            g_sqr = state["g_sqr"].at[wid].add(g2)
            hist = g_sqr[wid]
        else:
            g_sqr = state["g_sqr"] + g2
            hist = g_sqr
        step = delta * rho / (jnp.sqrt(hist) + self.eps)
        return data - step, {"g_sqr": g_sqr}


class AdamUpdater(Updater):
    name = "adam"

    def __init__(self, num_workers: int = 1, beta1: float = 0.9,
                 beta2: float = 0.999, eps: float = 1e-8):
        super().__init__(num_workers)
        self.beta1, self.beta2, self.eps = beta1, beta2, eps

    def init_state(self, shape, dtype):
        return {
            "m": jnp.zeros(shape, dtype),
            "v": jnp.zeros(shape, dtype),
            "t": jnp.zeros((), jnp.int32),
        }

    @_rule
    def apply(self, data, state, delta, opt):
        lr = jnp.asarray(opt.learning_rate, data.dtype)
        b1 = jnp.asarray(self.beta1, data.dtype)
        b2 = jnp.asarray(self.beta2, data.dtype)
        t = state["t"] + 1
        m = b1 * state["m"] + (1.0 - b1) * delta
        v = b2 * state["v"] + (1.0 - b2) * jnp.square(delta)
        tf = t.astype(data.dtype)
        m_hat = m / (1.0 - jnp.power(b1, tf))
        v_hat = v / (1.0 - jnp.power(b2, tf))
        step = lr * m_hat / (jnp.sqrt(v_hat) + self.eps)
        return data - step, {"m": m, "v": v, "t": t}


class FTRLUpdater(Updater):
    """FTRL-proximal (ref: Applications/LogisticRegression/src/updater/
    updater.cpp:79-101 FTRL branch + util/ftrl_sparse_table.h z/n entries).
    The delta passed to ``apply`` is the raw gradient; the stored data is the
    *weight* vector recomputed from the (z, n) state after each update, so
    Get keeps returning ready-to-use weights like every other table."""

    name = "ftrl"

    def __init__(self, num_workers: int = 1, alpha: float = 0.1,
                 beta: float = 1.0, lambda1: float = 0.1,
                 lambda2: float = 1.0):
        super().__init__(num_workers)
        self.alpha, self.beta = alpha, beta
        self.lambda1, self.lambda2 = lambda1, lambda2

    def init_state(self, shape, dtype):
        return {"z": jnp.zeros(shape, dtype), "n": jnp.zeros(shape, dtype)}

    @_rule
    def apply(self, data, state, delta, opt):
        g = delta
        z, n = state["z"], state["n"]
        alpha = jnp.asarray(self.alpha, data.dtype)
        sigma = (jnp.sqrt(n + jnp.square(g)) - jnp.sqrt(n)) / alpha
        z = z + g - sigma * data
        n = n + jnp.square(g)
        w = jnp.where(
            jnp.abs(z) <= self.lambda1,
            jnp.zeros_like(z),
            -(z - jnp.sign(z) * self.lambda1)
            / ((self.beta + jnp.sqrt(n)) / alpha + self.lambda2))
        return w, {"z": z, "n": n}


# classification used by the serving/coalescing planes (EXACT type match
# everywhere: a user subclass overriding apply() must not inherit either
# property):
# * STATELESS_LINEAR: Add is a signed accumulate with no state — K adds
#   merge into one summed add EXACTLY, and host-backed shards may apply
#   with in-place numpy instead of a jitted program.
# * OPT_INSENSITIVE: apply() never reads AddOption — queued adds coalesce
#   across senders regardless of per-worker opt values.
# * ROW_LOCAL_STATE: apply() is per-row elementwise and every state leaf
#   is row-aligned (gathered/scattered with the touched rows), so applying
#   K DISJOINT-row adds as one merged update is bit-identical to K
#   sequential applies — the invariant the send window's merging (client
#   groups + shard waves) relies on. Adam is excluded: its global step
#   counter t advances once per apply() CALL, so a merge would miscount
#   K-1 steps. Unlisted custom updaters never merge (conservative).
STATELESS_LINEAR: Dict[type, float] = {Updater: 1.0, SGDUpdater: -1.0}
OPT_INSENSITIVE = {Updater, SGDUpdater, FTRLUpdater}
ROW_LOCAL_STATE = {Updater, SGDUpdater, MomentumUpdater, AdaGradUpdater,
                   FTRLUpdater}

_REGISTRY: Dict[str, Callable[..., Updater]] = {
    "default": Updater,
    "sgd": SGDUpdater,
    "momentum_sgd": MomentumUpdater,
    "adagrad": AdaGradUpdater,
    "adam": AdamUpdater,
    "ftrl": FTRLUpdater,
}


def register_updater(name: str, factory: Callable[..., Updater]) -> None:
    """User extension point (the reference's factory is closed; ours is open)."""
    _REGISTRY[name] = factory


def get_updater(name: str, num_workers: int = 1, dtype=None, **kwargs) -> Updater:
    """Factory keyed on the ``updater_type`` flag value
    (ref src/updater/updater.cpp:38-46). Integer tables always get the default
    updater, matching ref updater.cpp:33-36."""
    if dtype is not None and jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        return Updater(num_workers)
    try:
        factory = _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown updater_type {name!r}; known: {sorted(_REGISTRY)}"
        ) from None
    return factory(num_workers=num_workers, **kwargs)
