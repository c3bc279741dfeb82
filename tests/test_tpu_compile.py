"""The main-path Pallas kernels compile for a TPU v5e.

Each case lowers and compiles one kernel with the TPU compiler for a
described ``v5e:2x2`` topology, with no chip attached: nothing runs, but
what Mosaic or the TPU compiler would refuse on the chip (a load from an
``ANY``-space ref, an unsupported cast, a block shape, more VMEM than a
kernel may use) fails here.  Shapes are the paper's MLP at its own width
(``configs/mlp_mnist.py``), and for the server step also Granite Code
8B's leaves.  The topology is described inside a fixture,
never at import, so every test worker collects the same tests and only
the worker that runs this file loads the TPU library.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs import mlp_mnist as cfg
from repro.kernels import compress, secure_agg, sketch, ssca_update

N_PARAMS = cfg.K * cfg.J + cfg.J + cfg.J * cfg.L + cfg.L
ROWS = -(-N_PARAMS // 128)              # the flattened model as (R, 128)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's compile cannot be read back from the persistent
    # cache, so keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile_for_chip(fn, sharding, *shapes, batch=None):
    """Compile ``fn`` for the described chip (under ``vmap`` over a
    leading client axis of ``batch`` when given) and check that a Mosaic
    kernel, not an XLA fallback, is in the program."""
    if batch is not None:
        fn = jax.vmap(fn)
        shapes = [((batch,) + s, d) for s, d in shapes]
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


# (shape, with β): the paper's MLP flattened to (R, 128) with β (λ > 0),
# and Granite Code 8B's leaves in their own shapes (FFN, attention
# output, key-value projection, a norm gain), without β (λ = 0) and with it
SSCA_CASES = {"mlp": ((ROWS, 128), True)}
SSCA_CASES.update({
    f"{name}-{'beta' if beta else 'nobeta'}": (shape, beta)
    for name, shape in [("ffn", (4096, 14336)), ("attn-out", (4096, 4096)),
                        ("kv", (4096, 1024)), ("norm", (1, 4096))]
    for beta in (False, True)})


@pytest.mark.parametrize("shape,with_beta", SSCA_CASES.values(),
                         ids=SSCA_CASES.keys())
def test_ssca_update_compiles(one_chip, shape, with_beta):
    f32 = jnp.float32

    def step(w, lin, g, *rest):
        beta, scalars = rest if with_beta else (None, rest[0])
        return ssca_update.ssca_update_2d(w, lin, g, beta, scalars)

    _compile_for_chip(step, one_chip,
                      *[(shape, f32)] * (4 if with_beta else 3),
                      ((4,), f32))


# (local clients, cohort): the whole cohort on one chip, one device's
# share of a cohort of 512 on four, and a cohort whose pending uploads
# do not fit VMEM even 8 rows at a time (the directed schedule)
@pytest.mark.parametrize("i_loc,alive,num_clients", [
    pytest.param(10, False, 10, id="10-False"),
    pytest.param(10, True, 10, id="10-True"),
    pytest.param(64, False, 64, id="64-False"),
    pytest.param(512, False, 512, id="512-False"),
    pytest.param(128, True, 512, id="128of512-True"),
    pytest.param(3072, False, 3072, id="3072-False-directed"),
])
def test_masked_sum_compiles(one_chip, i_loc, alive, num_clients):
    assert secure_agg._plan(i_loc, num_clients, ROWS).memo \
        == (i_loc < 3000)
    n_scalars = 3 + num_clients if alive else 3
    fn = functools.partial(secure_agg.masked_sum_2d, scale_bits=20,
                           num_clients=num_clients, with_alive=alive)
    _compile_for_chip(fn, one_chip, ((i_loc, ROWS, 128), jnp.float32),
                      ((n_scalars,), jnp.uint32))


def test_masked_sum_keeps_its_name_under_the_combine_scope(one_chip):
    """The engine traces the combine under ``jax.named_scope``
    (``secure_combine``): the scope lands in the op's metadata, and the
    kernel's instruction keeps the name a trace reader matches it by."""
    fn = functools.partial(secure_agg.masked_sum_2d, scale_bits=20,
                           num_clients=10, with_alive=False)

    def combine(msgs, scalars):
        with jax.named_scope("secure_combine"):
            return fn(msgs, scalars)

    args = [jax.ShapeDtypeStruct((10, ROWS, 128), jnp.float32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((3,), jnp.uint32, sharding=one_chip)]
    text = jax.jit(combine).lower(*args).compile().as_text()
    kernel = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(kernel) == 1
    assert re.search(r"%masked_sum_2d(\.\d+)? = ", kernel[0])
    assert "/secure_combine/" in kernel[0]


# Granite Code 8B's upload leaves at published widths, one layer and an
# eighth of the vocabulary (cell C's share), in tree order
GRANITE_UPLOAD = {
    "blocks": {"attn_norm": (1, 4096), "ffn_norm": (1, 4096),
               "wd": (1, 14336, 4096), "wg": (1, 4096, 14336),
               "wk": (1, 4096, 1024), "wo": (1, 4096, 4096),
               "wq": (1, 4096, 4096), "wu": (1, 4096, 14336),
               "wv": (1, 4096, 1024)},
    "embed": (6144, 4096), "final_norm": (4096,)}


def test_secure_combine_reads_each_granite_leaf_in_its_own_view(one_chip):
    """Four clients' weighted uploads of Granite's 11 leaves reach the
    combine kernel as they are: one kernel call a leaf, each message
    straight from the weighting fusion (or a bitcast of it), with no
    flat (S, n) copy of the upload and no relayout, concatenation or
    copy before the kernel."""
    from repro.kernels import ops

    def combine(msgs, weights, key_data):
        with jax.named_scope("secure_combine"):
            msgs = jax.tree.map(
                lambda m: m * weights.reshape((4,) + (1,) * (m.ndim - 1)),
                msgs)
            return ops.secure_quant_sum(msgs, key_data, scale_bits=20,
                                        use_kernel=True)

    f32 = jnp.float32
    msgs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((4,) + s, f32, sharding=one_chip),
        GRANITE_UPLOAD, is_leaf=lambda s: isinstance(s, tuple))
    text = jax.jit(combine).lower(
        msgs, jax.ShapeDtypeStruct((4,), f32, sharding=one_chip),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
    ).compile().as_text()
    # instruction name -> (opcode, first operand)
    producer = {m.group(1): (m.group(2), m.group(3)) for m in re.finditer(
        r"^\s*(?:ROOT )?%(\S+) = .*? ([\w-]+)\(%?([^,)\s]*)",
        text, re.M)}
    kernels = [ln for ln in text.splitlines()
               if re.search(r"%masked_sum_2d(\.\d+)? = ", ln)]
    assert len(kernels) == 11
    assert all("/secure_combine/" in ln for ln in kernels)
    assert "f32[4,243281920]" not in text
    for ln in kernels:
        name = re.search(r"custom-call\(%([^,)\s]+)", ln).group(1)
        # through the views a multi-output fusion or a bitcast gives
        while producer[name][0] in ("get-tuple-element", "bitcast"):
            name = producer[name][1]
        assert producer[name][0] not in ("copy", "concatenate"), ln
        assert not re.match(r"(copy|concatenate)", name), ln


@pytest.mark.parametrize("batch", [None, cfg.I], ids=["one", "vmap"])
@pytest.mark.parametrize("quantize,masked", [(True, False), (False, True)],
                         ids=["quantize", "topk_mask"])
def test_compress_compiles(one_chip, quantize, masked, batch):
    fn = functools.partial(compress.compress_2d_kernel, lbound=127,
                           quantize=quantize, masked=masked)
    _compile_for_chip(fn, one_chip, ((ROWS, 128), jnp.float32),
                      ((2,), jnp.uint32), ((2,), jnp.float32), batch=batch)


@pytest.mark.parametrize("rows,cols,batch", [(5, 256, None),
                                             (5, 4096, None),
                                             (4, 512, cfg.I)])
def test_sketch_encode_compiles(one_chip, rows, cols, batch):
    fn = functools.partial(sketch.sketch_encode_kernel, rows=rows,
                           cols=cols, scale_bits=20)
    _compile_for_chip(fn, one_chip, ((ROWS, 128), jnp.float32),
                      ((3,), jnp.uint32), batch=batch)
