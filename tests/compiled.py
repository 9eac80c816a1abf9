"""How a test compiles the two sides of a comparison.

A side run op by op compiles and dispatches every primitive on its own: a
toy model's gradient, or a plain reference's scan over positions, then costs
tens of seconds where the same side under one ``jax.jit`` costs a few.  Each
helper here returns one jitted program, the way the cells run theirs.
``jit``, ``value_and_grad`` and ``grad`` keep it for the session: the same
function (by identity) and arguments give the same jitted object back, and
``jax.jit`` caches a compile for each shape, so the cases of one shape share
one compile.  Hand them a function that lives as long as the module (a
module-level function, or one a fixture built), not a lambda made inside a
test, or nothing is shared.  A side whose Python loop unrolls into thousands
of operations may compile for minutes where it runs op by op in seconds:
time it cold before moving it here.

A new model's tests compare it with its reference through ``value_and_grad``
(the program's side) and ``reference`` (the reference's ``loss_and_grads`` or
``logits_of``, with its configuration dict closed over).  Its train step is
built once a file, over ``adamw()``: ``at_rate`` then hands each test a copy
of the state at the rate it needs, 0 to leave the weights as they are, and
every test runs the one compiled program.
"""

import functools

import jax
import jax.numpy as jnp
import optax


def _bound(fn, static):
    return functools.partial(fn, **static) if static else fn


@functools.cache
def jit(fn, /, **static):
    """``jax.jit`` of ``fn(*args, **static)``: the keyword arguments (a
    chunk size, an ``interpret`` flag, a function) are fixed, as
    ``static_argnames`` would fix them."""
    return jax.jit(_bound(fn, static))


@functools.cache
def value_and_grad(fn, /, argnums=0, has_aux=False, **static):
    """``jax.jit(jax.value_and_grad(fn, ...))`` of ``fn(*args, **static)``."""
    return jax.jit(jax.value_and_grad(_bound(fn, static), argnums=argnums, has_aux=has_aux))


@functools.cache
def grad(fn, /, argnums=0, has_aux=False, **static):
    """``jax.jit(jax.grad(fn, ...))`` of ``fn(*args, **static)``."""
    return jax.jit(jax.grad(_bound(fn, static), argnums=argnums, has_aux=has_aux))


def reference(fn, cfg):
    """A plain reference's ``fn(*args, cfg)`` as one program: ``cfg`` (a
    dict, which ``jax.jit`` cannot take as a static argument) is closed
    over, and ``jax.default_matmul_precision("highest")`` is entered inside
    the traced function, where the products it binds read it."""
    def run(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args, cfg)
    return jax.jit(run)


def adamw():
    """``optax.adamw`` with its learning rate as state (0 until ``at_rate``
    says otherwise), so that one compiled step serves every rate."""
    return optax.inject_hyperparams(optax.adamw)(learning_rate=0.0)


def at_rate(state, rate):
    """A copy of ``state`` (whose ``tx`` is ``adamw()``) with its AdamW
    begun afresh at ``rate``: what ``state.replace(tx=optax.adamw(rate),
    opt_state=optax.adamw(rate).init(state.params))`` holds, for the same
    compiled step.  The step donates its state, so each test steps its own."""
    state = jax.tree_util.tree_map(jnp.copy, state)
    opt = state.tx.init(state.params)
    rate = jnp.full_like(opt.hyperparams["learning_rate"], rate)
    return state.replace(opt_state=opt._replace(hyperparams={**opt.hyperparams, "learning_rate": rate}))
