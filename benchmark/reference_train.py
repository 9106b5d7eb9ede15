"""The plain side of a training cell's ``correct``: matmul precisions, the
optimizers written out, the first steps followed, and the comparison.

Nothing here imports the program. A family gives ``loss_fn(params, state,
batch, mm, conv) -> (loss, new_state)`` in plain ``jax.numpy``; this file
drives it with Adam or Nesterov momentum as their papers state them and
returns what the program's first steps are held against.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _fp8(x):
    """Per-tensor absmax-scaled round trip through float8 e4m3: the step a
    later PR would be tempted to take below bfloat16."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def _contract(op, quant):
    """``op(a, b)`` in float32 at ``highest``; with ``quant`` both operands
    of the forward and of the two backward contractions pass through it."""
    if quant is None:
        return op

    @jax.custom_vjp
    def f(a, b):
        return op(quant(a), quant(b))

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        _, vjp = jax.vjp(op, quant(a), quant(b))
        return vjp(quant(g))

    f.defvjp(fwd, bwd)
    return f


def contractions(precision: str):
    """(matmul, conv) for ``float32`` (the reference), ``fp8`` (its control)
    or ``bfloat16`` (operands rounded as the configuration's own compute
    type rounds them: a witness, see calibrate.py). ``conv(x, w, stride,
    padding)`` is NHWC x HWIO."""
    quant = {"float32": None, "bfloat16": lambda x: x.astype(jnp.bfloat16).astype(jnp.float32),
             "fp8": _fp8}[precision]

    def mm(a, b):
        return jnp.matmul(a, b, precision=HIGHEST)

    def conv(x, w, stride, padding):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)

    def conv_op(stride, padding):
        return _contract(functools.partial(conv, stride=stride, padding=padding), quant)

    qmm = _contract(mm, quant)
    return qmm, lambda x, w, stride, padding: conv_op(stride, padding)(x, w)


def _adam(opt, params, grads, moments, t):
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], opt["lr"]
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, moments[0], grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, moments[1], grads)
    new = jax.tree.map(
        lambda p, m_, v_: p - lr * (m_ / (1 - b1 ** t)) / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps),
        params, m, v)
    return new, (m, v)


def _nesterov(opt, params, grads, moments, t):
    mu, lr = opt["momentum"], opt["lr"]
    trace = jax.tree.map(lambda tr, g: g + mu * tr, moments[0], grads)
    new = jax.tree.map(lambda p, g, tr: p - lr * (g + mu * tr), params, grads, trace)
    return new, (trace,)


#: name -> (update, number of moment trees, all zero before the first step)
OPTIMIZERS = {"adam": (_adam, 2), "nesterov": (_nesterov, 1)}


def _f32(leaf) -> np.ndarray:
    """One leaf on the host in float32, from the host or from the device."""
    return np.asarray(leaf, np.float32)


def _norm(x: np.ndarray) -> float:
    flat = x.ravel().astype(np.float64)
    return float(np.sqrt(flat @ flat))


def leaf_norms(tree) -> np.ndarray:
    """Euclidean norm of every leaf, in the tree's flattening order. The
    tree may sit on the host or on the device; it is read one leaf at a time
    and worked on the host, so comparing costs the device nothing."""
    return np.array([_norm(_f32(x)) for x in jax.tree.leaves(tree)], np.float64)


def diff_norms(a, b) -> np.ndarray:
    """Norm of ``a - b`` leaf by leaf, streamed like ``leaf_norms``."""
    return np.array([_norm(_f32(x) - _f32(y)) for x, y in
                     zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True)], np.float64)


def _start_copies(leaves):
    for leaf in leaves:
        if hasattr(leaf, "copy_to_host_async"):
            leaf.copy_to_host_async()  # all copies in flight before the first is waited for


def to_host(tree):
    """A copy of ``tree`` on the host that shares nothing with the device's
    arrays (on the CPU backend ``device_get`` hands out a view, and a buffer
    with a view on it cannot be donated)."""
    leaves, treedef = jax.tree.flatten(tree)
    _start_copies(leaves)
    return jax.tree.unflatten(treedef, [np.array(leaf) for leaf in leaves])


def change(new, old):
    """``new - old`` leaf by leaf as float32 host arrays, in the tree of ``new``.

    Where both leaves sit on the device the difference is taken there and
    only it comes to the host, one leaf at a time: reading the program's own
    arrays would leave a host copy cached on each of them, and the step that
    later donates them pays for freeing it (0.4 GB for BERT-base, at the
    window's first step, when the device has nothing queued: 60-80 ms idle,
    a step lost; my chip run, PR 28). Otherwise both are read to the host."""
    leaves, treedef = jax.tree.flatten(new)
    pairs = list(zip(leaves, jax.tree.leaves(old), strict=True))
    on_device = [isinstance(u, jax.Array) and isinstance(v, jax.Array) for u, v in pairs]
    _start_copies(x for pair, there in zip(pairs, on_device) if not there for x in pair)
    return jax.tree.unflatten(treedef, [
        np.asarray(u.astype(jnp.float32) - v.astype(jnp.float32)) if there else _f32(u) - _f32(v)
        for (u, v), there in zip(pairs, on_device)])


def follow(loss_fn, params, state, batches, opt, precision="float32", transform=None):
    """Drive ``loss_fn`` through ``len(batches)`` steps from ``params``, in place.

    Returns the readings of ``compare`` as host arrays: each step's loss,
    the first gradient, and the change of (params, state) after the last
    step. On the device this holds at its peak the parameters, the moments
    and one gradient (16 bytes a parameter under Adam) plus one step's
    activations: ``params`` and ``state`` are **donated** (the caller's
    arrays are gone when this returns), the start goes to the host before the
    first step, and each step's gradient leaves the device before the next
    step runs. The start is copied rather than made again from the seed so
    that this file needs no family and no second ``init_params`` program.

    ``transform(step)`` wraps the compiled step, ``step(params, state,
    moments, batch, t) -> (params, state, moments, loss, grads)``, and is
    called once a step outside ``jit``: a test plants a fault in this side
    with it, or reads what the device holds between steps (tests/yardstick).
    """
    mm, conv = contractions(precision)
    update, n_moments = OPTIMIZERS[opt["name"]]

    def step(params, state, moments, batch, t):
        (loss, new_state), grads = jax.value_and_grad(
            lambda p: loss_fn(p, state, batch, mm, conv), has_aux=True)(params)
        new_params, moments = update(opt, params, grads, moments, t)
        return new_params, new_state, moments, loss, grads

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    if transform is not None:
        step = transform(step)
    start = to_host((params, state))
    moments = tuple(jax.tree.map(jnp.zeros_like, params) for _ in range(n_moments))
    losses, first = [], None
    for t, batch in enumerate(batches, start=1):
        params, state, moments, loss, grads = step(params, state, moments, batch, jnp.float32(t))
        losses.append(float(loss))
        if first is None:
            first = to_host(grads)
        del grads
    return {"losses": losses, "grad": first, "delta": change((params, state), start)}


def leaf_table(got: dict, want: dict) -> dict:
    """Per leaf, program (``got``) against reference (``want``): the gap of
    the two norms and the norm of the difference, of the first gradient
    (parameter leaves) and of the change after the last step (parameter, then
    state leaves), each over the reference's norm of that leaf or of the
    median leaf, whichever is larger; and which leaves count for the change.

    Leaves whose first gradient in the reference is under a thousandth of
    the median leaf's (a key's bias under softmax) move by round-off alone
    and are left out of the change; state leaves (no gradient) stay in.
    """
    g_ref, d_ref = leaf_norms(want["grad"]), leaf_norms(want["delta"])
    g_floor, d_floor = np.maximum(g_ref, np.median(g_ref)), np.maximum(d_ref, np.median(d_ref))
    live = g_ref >= 1e-3 * np.median(g_ref)
    return {"grad_gap": np.abs(leaf_norms(got["grad"]) - g_ref) / g_floor,
            "grad_diff": diff_norms(got["grad"], want["grad"]) / g_floor,
            "delta_gap": np.abs(leaf_norms(got["delta"]) - d_ref) / d_floor,
            "delta_diff": diff_norms(got["delta"], want["delta"]) / d_floor,
            "grad_floor": g_floor,
            "keep": np.concatenate([live, np.ones(len(d_ref) - len(live), bool)]),
            "is_state": np.arange(len(d_ref)) >= len(g_ref)}


def compare(got: dict, want: dict) -> dict:
    """The numbers a training cell can be held to (``limits/<cell>.json``
    says which), program (``got``) against reference (``want``).

    ``*_norm_gap`` is the gap between the two norms, ``*_diff`` the norm of
    the difference: what random rounding adds to a gradient barely moves its
    norm and shows in the difference. Worst leaf unless named ``_median``.
    """
    n = min(len(got["losses"]), len(want["losses"]))
    t = leaf_table(got, want)
    keep, state = t["keep"], t["is_state"]
    out = {"loss_gap": max(abs(g - w) / max(abs(w), 1e-12)
                           for g, w in zip(got["losses"][:n], want["losses"][:n])),
           "grad_norm_gap": np.max(t["grad_gap"]), "grad_norm_gap_median": np.median(t["grad_gap"]),
           "delta_norm_gap": np.max(t["delta_gap"][keep]),
           "delta_norm_gap_median": np.median(t["delta_gap"][keep]),
           "grad_diff_median": np.median(t["grad_diff"]), "grad_diff_worst": np.max(t["grad_diff"]),
           "delta_diff_median": np.median(t["delta_diff"][keep & ~state])}
    if state.any():
        out["state_diff_median"] = np.median(t["delta_diff"][state])
    if "grad_rounded" in want:
        # the same difference counted in roundings: over what the reference itself moves by when
        # its operands are rounded to the configuration's compute type, leaf by leaf. How far a
        # gradient sits above its own noise differs from seed to seed; this does not.
        noise = diff_norms(want["grad_rounded"], want["grad"]) / t["grad_floor"]
        out["grad_diff_roundings"] = np.median(t["grad_diff"] / np.maximum(noise, max(1e-3 * np.median(noise), 1e-30)))
    return {name: float(value) for name, value in out.items()}
