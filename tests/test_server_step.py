"""The fused server step runs the kernel on each parameter leaf's own
view, and at λ = 0 neither carries nor streams β.  Both changes leave
every number as it was: compared bit for bit with the kernel run once on
the whole model concatenated into one flat buffer, with β, which is
what the step did before."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import protocol, ssca
from repro.core.schedules import paper_schedules
from repro.kernels import ops
from repro.kernels import ssca_update as su

LANES = su.LANES


def _flat_update(params, lin, grads, beta, scalars):
    """The step on one flat buffer: every leaf concatenated, padded to
    128 lanes, the kernel with β, and the result sliced apart again."""
    leaves, treedef = jax.tree_util.tree_flatten(params)

    def flat(tree):
        v = jnp.concatenate([x.astype(jnp.float32).reshape(-1)
                             for x in jax.tree.leaves(tree)])
        return jnp.pad(v, (0, (-v.size) % LANES)).reshape(-1, LANES)

    outs = su.ssca_update_2d(flat(params), flat(lin), flat(grads),
                             flat(beta), scalars, interpret=True)

    def unflat(v):
        v, out, off = v.reshape(-1), [], 0
        for x in leaves:
            out.append(v[off:off + x.size].reshape(x.shape).astype(x.dtype))
            off += x.size
        return jax.tree_util.tree_unflatten(treedef, out)

    return tuple(unflat(v) for v in outs)


def _tree(key, shapes):
    ks = jax.random.split(key, len(shapes))
    return {f"p{i}": jax.random.normal(k, s)
            for i, (k, s) in enumerate(zip(ks, shapes))}


# the paper's MLP (784 -> 128 -> 10, no biases: two leaves whose sizes
# are multiples of 128), leaves that need padding, and leaves whose last
# dim is a multiple of 128 (run in their own shape, in tiles of several
# column blocks)
SHAPES = {"mlp": [(128, 784), (10, 128)],
          "padded": [(37, 5), (11,), (3, 128), (130,)],
          "wide": [(4, 384), (2, 3, 4352), (1, 2048)]}


def _assert_bits(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _positive_lambda(shapes):
    k = jax.random.split(jax.random.key(7), 4)
    w, lin, g, beta = (_tree(kk, shapes) for kk in k)
    rho, gamma, tau, lam = 0.4, 0.3, 0.1, 1e-5
    scalars = jnp.asarray([rho, gamma, tau, lam], jnp.float32)
    new = ops.ssca_update(w, lin, g, beta, rho=rho, gamma=gamma, tau=tau,
                          lam=lam, interpret=True)
    return new, _flat_update(w, lin, g, beta, scalars)


@pytest.mark.parametrize("shapes", ["mlp", "padded"])
def test_per_leaf_kernel_is_bit_equal_at_positive_lambda(shapes):
    """λ = 1e-5, as in the MLP cells: ω', lin' and β' bit-equal."""
    _assert_bits(*_positive_lambda(SHAPES[shapes]))


def test_wide_leaves_agree_at_positive_lambda():
    """Leaves run in their own shape take tiles of another width, and
    the CPU's interpret mode may then fuse a multiply and an add into one
    rounding where the flat run did two: the terms are O(1), so the two
    agree to about one float32 rounding of them (measured 1.2e-7)."""
    new, flat = _positive_lambda(SHAPES["wide"])
    for x, y in zip(jax.tree.leaves(new), jax.tree.leaves(flat),
                    strict=True):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=0,
                                   atol=1e-6)


@pytest.mark.parametrize("shapes", SHAPES.values(), ids=SHAPES.keys())
def test_kernel_without_beta_is_bit_equal_at_zero_lambda(shapes):
    """λ = 0: the variant without β gives the ω' and lin' of the kernel
    that streamed a β, bit for bit."""
    k = jax.random.split(jax.random.key(8), 4)
    w, lin, g, beta = (_tree(kk, shapes) for kk in k)
    rho, gamma, tau = 0.4, 0.3, 1.0
    scalars = jnp.asarray([rho, gamma, tau, 0.0], jnp.float32)
    w2, l2, b2 = ops.ssca_update(w, lin, g, None, rho=rho, gamma=gamma,
                                 tau=tau, lam=0.0, interpret=True)
    assert b2 is None
    we, le, _ = _flat_update(w, lin, g, beta, scalars)
    _assert_bits((w2, l2), (we, le))


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "tree-map"])
def test_zero_lambda_state_carries_no_beta(fused):
    """Algorithm 1 at λ = 0 keeps no β, and five server steps give the
    ω and lin of the same steps on a state that carried one."""
    params = _tree(jax.random.key(9), SHAPES["padded"])
    rho, gamma = paper_schedules(1)
    hp = ssca.SSCAHyperParams(tau=1.0, lam=0.0, rho=rho, gamma=gamma)
    alg = protocol.SSCAUnconstrained(loss_fn=None, hp=hp, fused=fused)
    new_state = alg.init_state(params)
    assert new_state.beta is None
    old_state = ssca.init(params, with_beta=True)
    p_new = p_old = params
    for t in range(5):
        g = _tree(jax.random.key(100 + t), SHAPES["padded"])
        p_new, new_state = ssca.server_update(new_state, p_new, g, hp,
                                              fused=fused, interpret=True)
        if fused:
            # before: the kernel with β, on one flat buffer
            tt = old_state.step.astype(jnp.float32)
            scalars = jnp.asarray([hp.rho(tt), hp.gamma(tt), hp.tau, 0.0],
                                  jnp.float32)
            p_old, lin, _ = _flat_update(p_old, old_state.lin, g,
                                         old_state.beta, scalars)
            old_state = old_state._replace(step=old_state.step + 1, lin=lin)
        else:
            p_old, old_state = ssca.server_update(old_state, p_old, g, hp)
        _assert_bits((p_new, new_state.lin), (p_old, old_state.lin))
    assert new_state.beta is None


def test_positive_lambda_needs_beta():
    params = {"w": jnp.ones((4, 128))}
    hp = ssca.SSCAHyperParams(tau=0.1, lam=1e-5)
    with pytest.raises(ValueError, match="β"):
        ssca.server_update(ssca.init(params, with_beta=False), params,
                           params, hp, fused=True, interpret=True)
