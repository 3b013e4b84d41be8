"""Compile the main path's kernels for a described TPU v5e, at the published
widths of ``configs/chimera_dataplane.py`` (d_model 256, 4 heads of 64,
m=256, d_v 64, L=64, 24 signature words).

Nothing here runs: each test compiles for a chip that is described and not
attached, so Mosaic refuses here what it would refuse on the chip.  The
topology is described inside a module-scoped fixture (never on import), and
the persistent compilation cache is off around these compiles, since an
entry written for a described chip cannot be read back without one.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# the widths the tests compile at (chimera-dataplane, full width)
BH, GQ, D, M, DV, L = 1024, 1, 64, 256, 64, 64
T = 1024


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip; skips where no topology can be described."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree,
    )


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.fixture(scope="module")
def full_width():
    """The served classifier config at full width, with its compiled
    signature layout, plus abstract params and rules."""
    from repro.compile.passes import required_sig_words
    from repro.core.symbolic import RuleSet
    from repro.launch.flow_serve import classifier_config
    from repro.train import classifier as C

    ccfg = classifier_config()
    ccfg = dataclasses.replace(ccfg, sig_words=required_sig_words(
        ccfg.arch.vocab_size, ccfg.marker_base
    ))
    params = jax.eval_shape(
        lambda k: C.init_classifier(ccfg, k)[0], jax.random.PRNGKey(0)
    )
    W = ccfg.sig_words
    rules = RuleSet(
        values=jax.ShapeDtypeStruct((1, W), jnp.uint32),
        masks=jax.ShapeDtypeStruct((1, W), jnp.uint32),
        weights=jax.ShapeDtypeStruct((1,), jnp.float32),
        hard=jax.ShapeDtypeStruct((1,), bool),
    )
    return ccfg, params, rules


def test_decode_step_kernel_compiles(chip):
    from repro.kernels.decode_step.kernel import decode_step_pallas

    f32 = jnp.float32
    s = lambda *shape, dt=f32: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    args = (s(BH, GQ, D), s(BH, D), s(BH, DV), s(BH, GQ, M), s(BH, L, M),
            s(BH, L, D), s(BH, L, DV), s(BH, M, DV), s(BH, M),
            s(BH, dt=jnp.int32))
    text = decode_step_pallas.lower(*args, chunk_size=L).compile().as_text()
    assert "tpu_custom_call" in text


def test_chimera_attention_kernel_compiles(chip):
    from repro.kernels.chimera_attention.kernel import chimera_attention_pallas

    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=chip)
    text = _compiled_text(
        lambda q, k, v, pq, pk: chimera_attention_pallas(
            q, k, v, pq, pk, chunk_size=L
        ),
        s(4, GQ, T, D), s(4, T, D), s(4, T, DV), s(4, GQ, T, M), s(4, T, M),
    )
    assert "tpu_custom_call" in text


def test_flow_ingest_score_stage_compiles(chip, full_width):
    from repro.kernels.flow_ingest.kernel import flow_ingest_scores_pallas

    ccfg, params, rules = full_width
    lanes, d, W = 256, ccfg.arch.d_model, ccfg.sig_words
    text = _compiled_text(
        lambda p, r, pooled, sig, sticky: flow_ingest_scores_pallas(
            ccfg, p, r, pooled, sig, sticky
        ),
        _shapes(params, chip), _shapes(rules, chip),
        jax.ShapeDtypeStruct((lanes, d), jnp.float32, sharding=chip),
        jax.ShapeDtypeStruct((lanes, W), jnp.uint32, sharding=chip),
        jax.ShapeDtypeStruct((lanes,), bool, sharding=chip),
    )
    assert "tpu_custom_call" in text


def _fused_ingest_text(chip, full_width, n_slots, width, donate=()) -> str:
    """Compiled HLO of the fused ``pallas-tpu`` flow step at full width over
    ``n_slots`` table rows, one chunk bucket of 8 chunks of ``width`` lanes,
    at the benchmark's ``highest`` matmul precision."""
    from repro.kernels.dispatch import apply_kernel_backend, resolve
    from repro.serve.flow_engine import init_flow_caches

    ccfg, params, rules = full_width
    arch, _ = apply_kernel_backend(ccfg.arch, "pallas-tpu")
    ccfg = dataclasses.replace(ccfg, arch=arch)
    chunks, pkt_len = 8, 16
    caches = jax.eval_shape(lambda: init_flow_caches(arch, n_slots, 1024))
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    fused = resolve("flow_ingest", "pallas-tpu")(ccfg, n_slots, 1024)
    with jax.default_matmul_precision("highest"):
        return jax.jit(fused, donate_argnums=donate).lower(
            _shapes(params, chip), _shapes(rules, chip),
            _shapes(caches, chip), s((n_slots,), jnp.int32),
            s((n_slots, ccfg.sig_words), jnp.uint32),
            s((n_slots, arch.d_model), jnp.float32), s((n_slots,), bool),
            s((chunks, width), jnp.int32),
            s((chunks, width, pkt_len), jnp.int32),
            s((chunks, width), bool), s((), jnp.int32),
        ).compile().as_text()


def test_fused_pallas_tpu_ingest_holds_a_kernel(chip, full_width):
    """The fused ``pallas-tpu`` flow step at full width (small capacity):
    its score stage must compile to a Mosaic kernel, not to XLA."""
    text = _fused_ingest_text(chip, full_width, n_slots=65, width=16)
    assert "tpu_custom_call" in text


def _table_copies_outside_entry(text: str, n_slots: int):
    """Names of the layout ``copy`` ops on a whole-table array (one with an
    ``n_slots`` dimension) in any computation but ``ENTRY``.  The
    ``copy-start``/``copy-done`` pairs that move arrays between memory
    spaces are another opcode and are not counted."""
    found, in_entry = [], False
    for line in text.splitlines():
        if line[:1] not in ("", " ", "}") and line.rstrip().endswith("{"):
            in_entry = line.startswith("ENTRY")
            continue
        m = re.search(r"%(\S+) = \w+\[([\d,]*)\]\S* copy\(", line)
        if m and not in_entry and str(n_slots) in m.group(2).split(","):
            found.append(m.group(1))
    return found


@pytest.mark.parametrize("width", [8, 256])
def test_fused_ingest_loop_carries_the_table_without_relayout(
    chip, full_width, width
):
    """With the engine's donation of the table (args 2-6), the chunk loop
    of the fused step holds no ``copy`` of a whole-table array: the table
    is stored slot-major, the layout its gather and scatter by slot use, so
    no chunk pays a relayout of every row."""
    n_slots = 65
    text = _fused_ingest_text(chip, full_width, n_slots, width,
                              donate=(2, 3, 4, 5, 6))
    assert " while(" in text  # the chunk loop is there to look inside
    assert _table_copies_outside_entry(text, n_slots) == []


def _cache_leaf_copies(text: str, n_slots: int):
    """Names of the layout ``copy`` ops, in any computation ``ENTRY``
    included, on a float array of rank 4 or more with an ``n_slots``
    dimension: a whole-table decode-cache leaf, relaid out as a launch
    enters or leaves."""
    found = []
    for m in re.finditer(r"%(\S+) = f\d+\[([\d,]*)\]\S* copy\(", text):
        dims = m.group(2).split(",")
        if len(dims) >= 4 and str(n_slots) in dims:
            found.append(m.group(1))
    return found


@pytest.mark.parametrize("width", [8, 256])
def test_fused_ingest_launch_takes_the_cache_table_as_stored(
    chip, full_width, width
):
    """With the engine's donation of the table (args 2-6), no computation
    of the fused step, ``ENTRY`` included, copies a whole-table cache leaf:
    the k/v rings are stored lane-dense (``stored_slot_shape``), so they
    enter the launch in the layout its chunk loop carries, and no launch
    relays the whole ring table out and back."""
    n_slots = 65
    text = _fused_ingest_text(chip, full_width, n_slots, width,
                              donate=(2, 3, 4, 5, 6))
    assert " while(" in text
    assert _cache_leaf_copies(text, n_slots) == []
