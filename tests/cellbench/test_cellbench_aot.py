"""The depth cut stays honest: the decode step and one prefill chunk of
every committed one-chip configuration, at the file's widths, depth,
slots and pool, compile for a described (not attached) TPU v5e, and
what the compiler says they need fits one chip's 16 GB beside the
weights and the pool they are given as arguments.

Rehearsal 3 of the on-chip-measurement guide. The topology is described
inside a fixture, in this one file: only one process may load libtpu,
and a module that decided at import whether its tests exist would give
pytest-xdist's workers different tests to collect.
"""

import functools
import glob
import json
import os

import jax
import jax.numpy as jnp
import pytest

from cellbench import serve_child

CONFIGS = sorted(glob.glob(os.path.join(
    os.path.dirname(serve_child.__file__), "configs", "*.json")))
#: usable HBM of one v5e chip as /state reported it (bytes_limit, PR 21)
HBM_BYTES = 15.75 * 2 ** 30


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu on this box
        pytest.skip(f"libtpu cannot describe a v5e topology: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip: keep it off here."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)


def _flag(flags, name, default):
    return type(default)(flags[flags.index(name) + 1]) \
        if name in flags else default


def _programs(doc):
    from aigw_tpu.models import llama, mixtral
    from aigw_tpu.models.quant import quantize_tensor

    cb = doc["cellbench"]
    family = {"llama": llama, "mixtral": mixtral}[cb["family"]]
    cfg = serve_child.config_class(cb["family"])(
        **serve_child.model_kwargs(doc))
    flags = cb["serve_flags"]
    B = _flag(flags, "--max-batch-size", 8)
    S = _flag(flags, "--max-seq-len", 2048)
    page = _flag(flags, "--page-size", 128)
    mode = _flag(flags, "--quantize", "")
    finish = (lambda n, w: quantize_tensor(n, w, mode)) if mode else None
    params = jax.eval_shape(lambda: family.init_params(
        jax.random.PRNGKey(0), cfg, finish=finish))
    P = S // page
    # the engine's pool: every slot's pages + the dump page
    kv = jax.ShapeDtypeStruct(
        (cfg.n_layers, 2, (B * P + 1) * page, cfg.n_kv_heads, cfg.head_dim),
        jnp.bfloat16)
    i32 = jnp.int32
    decode = (
        functools.partial(family.decode_step, cfg=cfg, page_size=page),
        dict(p=params, tokens=jax.ShapeDtypeStruct((B,), i32),
             positions=jax.ShapeDtypeStruct((B,), i32), kv_cache=kv,
             page_table=jax.ShapeDtypeStruct((B, P), i32),
             active=jax.ShapeDtypeStruct((B,), jnp.bool_)))
    chunk = _flag(flags, "--prefill-chunk-tokens", 256)
    prefill = (
        functools.partial(family.prefill_suffix, cfg=cfg, page_size=page),
        dict(p=params, tokens=jax.ShapeDtypeStruct((1, chunk), i32),
             prefix_lens=jax.ShapeDtypeStruct((1,), i32),
             seq_lens=jax.ShapeDtypeStruct((1,), i32), kv_cache=kv,
             page_table=jax.ShapeDtypeStruct((1, P), i32)))
    return {"decode": decode, "prefill_chunk": prefill}


@pytest.mark.parametrize("program", ["decode", "prefill_chunk"])
@pytest.mark.parametrize(
    "path", CONFIGS, ids=[os.path.basename(p)[:-5] for p in CONFIGS])
def test_fits_one_v5e_chip(v5e, no_compile_cache, path, program):
    with open(path) as f:
        doc = json.load(f)
    if doc["cellbench"]["chips"] != 1 or \
            doc["cellbench"]["expect"]["platform"] != "tpu":
        pytest.skip("not a one-chip TPU configuration")
    fn, shapes = _programs(doc)[program]
    placed = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=v5e),
        shapes)
    compiled = jax.jit(fn, donate_argnames=("kv_cache",)).lower(
        **placed).compile()
    m = compiled.memory_analysis()
    # arguments = weights + pool (+ the step's small inputs); the pool
    # is donated, so the output aliases it
    need = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    print(f"{os.path.basename(path)} {program}: arguments "
          f"{m.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.2f} GB, total {need / 1e9:.2f} GB")
    want = doc["cellbench"]["expect"]["param_bytes_total"]
    got = sum(s.size * s.dtype.itemsize
              for s in jax.tree_util.tree_leaves(shapes["p"]))
    assert abs(got - want) <= 0.02 * want, (got, want)
    assert need < HBM_BYTES, f"{need / 1e9:.2f} GB does not fit one chip"
