"""Compile the served path's programs at real widths for a described TPU
v5e, with no chip attached: the TPU compiler refuses here what it would
refuse on the chip (misaligned kernel blocks, programs that do not fit).

Nothing runs, so these tests say nothing about results or times.  The
topology is described inside a fixture, never at import: only one process
at a time may load the TPU library, and every test worker imports this
file.
"""
import contextlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.data import synthetic as syn
from repro.kernels import interaction, ops
from repro.layers import embedding as emb_lib
from repro.serve.models import served_forward, served_param_shapes

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _bitpacked_gathers(hlo: str) -> list[str]:
    """Instructions of the gather scope that pack a (table, row) index into
    one word, whatever the order of their attributes."""
    return [line for line in hlo.splitlines()
            if "GatherScatterIndicesBitpacked" in line
            and "/embedding_gather/" in line]


@pytest.mark.parametrize("bucket", [1, 64, 256])
def test_dlrm_rmc1_served_forward_compiles(one_chip, bucket):
    """The served forward on the params as ``recsys_model`` holds them:
    tables packed four 32-wide rows to a 128-lane row reach the program
    row-major and unpadded, so each lookup reads one contiguous row, not a
    row-minor table's four tiles."""
    cfg = configs.get("dlrm-rmc1").config
    params = _on(one_chip, served_param_shapes(cfg))
    assert params["tables"].shape == (10, 250_000, 128)
    batch = _on(one_chip, syn.recsys_specs(cfg, bucket, with_label=False))
    compiled = served_forward("tpu").lower(params, cfg, batch).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 10 * 1_000_000 * 32 * 4
    # 1,280,963,072 bytes of arguments: nothing padded to 128 lanes
    assert mem.argument_size_in_bytes < 1.282e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES
    hlo = compiled.as_text()
    # switching between bucket programs with this on halted a v5e
    assert "cross_program_prefetch" not in hlo
    assert re.search(r"%params__tables__\S* = f32\[10,250000,128\]"
                     r"\{2,1,0:T\(8,128\)\} parameter", hlo)
    assert not re.search(r"f32\[10,\d+,\d+\]\{1,2,0", hlo)
    # one flat gather: the gather batched over the packed tables, whose
    # (table, row) index XLA packs into one word, halted a v5e core
    assert not _bitpacked_gathers(hlo)


def test_the_bitpacked_gather_guard_catches_the_halting_form(one_chip):
    """The lookup batched over the packed tables, the form that halted a
    v5e core, compiles to a bit-packed index in the gather scope, and the
    served flat lookup at the same shapes does not."""
    cfg = configs.get("dlrm-rmc1").config
    tables = _on(one_chip, served_param_shapes(cfg)["tables"])
    ids = jax.ShapeDtypeStruct((64, 10, 80), jnp.int32, sharding=one_chip)

    def batched(t, i):
        with jax.named_scope("embedding_gather"):
            return jax.vmap(lambda t, i: jnp.take(t, i // 4, axis=0),
                            in_axes=(0, 1), out_axes=1)(t, i)

    def flat(t, i):
        with jax.named_scope("embedding_gather"):
            return emb_lib.take_rows(t, i, cfg.embed_dim, cfg.vocab)

    def hlo(fn):
        return jax.jit(fn).lower(tables, ids).compile().as_text()

    assert _bitpacked_gathers(hlo(batched))
    assert not _bitpacked_gathers(hlo(flat))


@pytest.mark.parametrize("bucket", [1, 64])
def test_dlrm_rmc1_named_scopes_change_no_program(one_chip, monkeypatch,
                                                  bucket):
    """The forward's named scopes (gather, bottom MLP, interaction, top
    MLP) reach the TPU program's metadata and nothing else."""
    cfg = configs.get("dlrm-rmc1").config

    def compiled() -> str:
        jax.clear_caches()
        params = _on(one_chip, served_param_shapes(cfg))
        batch = _on(one_chip, syn.recsys_specs(cfg, bucket, with_label=False))
        return served_forward("tpu").lower(params, cfg,
                                           batch).compile().as_text()

    def program(hlo: str) -> str:
        body = hlo.split("\nFileNames\n")[0]
        return re.sub(r", metadata=\{[^}]*\}", "", body)

    scoped = compiled()
    assert "jit(forward)/embedding_gather/" in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled()
    assert "embedding_gather" not in plain
    assert program(scoped) == program(plain)


@pytest.mark.parametrize("bucket", [1, 64, 256])
def test_dien_served_forward_compiles(one_chip, bucket):
    """DIEN at its published widths, served as ``recsys_model`` holds it:
    all ten 18-wide tables (eight fields, item and category) packed seven
    rows to a 128-lane row; every lookup in the gather scope, none with a
    bit-packed index; the GRU, the attention and the AUGRU each in its
    named scope inside ``interaction``, the GRU and the AUGRU each one
    Pallas kernel (``kernels/gru.py``) with no while loop left."""
    cfg = configs.get("dien").config
    params = _on(one_chip, served_param_shapes(cfg))
    assert params["tables"].shape == (8, 142_864, 128)
    assert params["item_tables"].shape == (2, 142_864, 128)
    batch = _on(one_chip, syn.recsys_specs(cfg, bucket, with_label=False))
    assert batch["history"].shape == (bucket, 100, 2)
    compiled = served_forward("tpu").lower(params, cfg, batch).compile()
    mem = compiled.memory_analysis()
    # 731,463,680 bytes of packed tables, nothing else near them
    assert 10 * 142_864 * 128 * 4 <= mem.argument_size_in_bytes < 7.4e8
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES
    hlo = compiled.as_text()
    assert "cross_program_prefetch" not in hlo
    assert not _bitpacked_gathers(hlo)
    gathers = [line for line in hlo.splitlines()
               if " gather(" in line and "slice_sizes={1,128}" in line]
    assert gathers and all("/embedding_gather/" in line for line in gathers)
    assert not [line for line in hlo.splitlines() if " gather(" in line
                and "slice_sizes={1,18}" in line]
    for scope in ("interest_extractor", "interest_attention",
                  "interest_evolution"):
        assert f"jit(forward)/interaction/{scope}/" in hlo, scope
    # each recurrence is one Pallas kernel in its scope, not a scan
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 2
    for scope, line in zip(("interest_extractor", "interest_evolution"),
                           kernels):
        assert f"/interaction/{scope}/" in line, scope
    assert " while(" not in hlo


def test_dot_interaction_kernel_compiles(one_chip):
    feats = jax.ShapeDtypeStruct((1024, 11, 128), jnp.float32,
                                 sharding=one_chip)
    compiled = jax.jit(interaction.dot_interaction).lower(feats).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batch", [8, 1024])
def test_embedding_bag_kernel_compiles(one_chip, batch):
    table = jax.ShapeDtypeStruct((1_000_000, 128), jnp.float32,
                                 sharding=one_chip)
    idx = jax.ShapeDtypeStruct((batch, 80), jnp.int32, sharding=one_chip)
    compiled = ops.embedding_bag.lower(table, idx).compile()
    assert "tpu_custom_call" in compiled.as_text()
