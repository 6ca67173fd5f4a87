"""The reference's training state, sharded over the cell's devices.

On the CPU: the tiny cell's reference trains its compared steps on one
device and, in a child process, on four host devices.  The readings the
comparison takes agree, and on four devices each leaf of the weights and
of both AdamW moments holds a quarter of its first dimension that four
divides on each device.

For a described TPU v5e 2x2 (no chip needed): the reference's gradient,
add and AdamW calls at the Mistral-7B-v0.3 four-layer share compile, and
each fits one chip beside the state it leaves resident.
"""
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import reference
import run
from model import load_config
from traffic import Traffic, load_mix

HERE = Path(__file__).resolve().parent
SEED = 2 ** 31 + 11

# The one- and four-device runs differ only in the order of float32 sums:
# the rows each call takes (padded to a multiple of the devices) and the
# reduction of the devices' partial gradients.  Such order effects read
# about 2e-07 of a number on the CPU; 1e-05 leaves fifty times that, and
# lies below every limit in `chipbench/limits/` (the tightest,
# `loss_gap` 6e-05), so a gap under it cannot change `correct`.
RTOL = 1e-5

CHILD = r"""
import json, sys
from pathlib import Path
import jax
import reference
from model import load_config
from traffic import Traffic, load_mix

root, seed, steps = Path(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
assert len(jax.devices()) == 4, jax.devices()
c = load_config("tiny", root / "chipbench")
tr = Traffic(load_mix("tiny", root / "chipbench"), c["vocab_size"], seed)
ref = reference.Reference(c, tr, jax.devices())
w, m, v = ref.init(seed)
w, m, v, *_ = ref.step(w, m, v, 0)
layout = {name: {reference.leaf_name(p): [list(x.shape), [
    [s.device.id, list(s.data.shape), [i.start or 0 for i in s.index]]
    for s in x.addressable_shards]]
    for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}
    for name, tree in (("w", w), ("m", m), ("v", v))}
del w, m, v
print("RESULT " + json.dumps({"readings": ref.run(seed, steps),
                              "layout": layout}))
"""


def _tiny_reference(root, devices):
    c = load_config("tiny", root / "chipbench")
    tr = Traffic(load_mix("tiny", root / "chipbench"), c["vocab_size"], SEED)
    return reference.Reference(c, tr, devices)


def _four_devices(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(HERE.parent), str(HERE)]))
    r = subprocess.run([sys.executable, "-c", CHILD, str(root), str(SEED),
                        str(run.CHECK_STEPS)], capture_output=True, text=True,
                       timeout=300, env=env)
    lines = [x for x in r.stdout.splitlines() if x.startswith("RESULT ")]
    assert r.returncode == 0 and lines, r.stdout[-2000:] + r.stderr[-4000:]
    return json.loads(lines[-1][len("RESULT "):])


def test_four_devices_agree_with_one_and_shard_the_state(tiny_root):
    one = _tiny_reference(tiny_root, jax.devices()[:1]).run(
        SEED, run.CHECK_STEPS)
    four = _four_devices(tiny_root)
    got = four["readings"]
    for k in ("loss", "gnorm"):
        np.testing.assert_allclose(got[k], one[k], rtol=RTOL, atol=0)
    for k in ("grad1", "delta"):
        assert set(got[k]) == set(one[k])
        for leaf in one[k]:
            np.testing.assert_allclose(got[k][leaf], one[k][leaf],
                                       rtol=RTOL, atol=0, err_msg=leaf)

    for tree, leaves in four["layout"].items():
        assert set(leaves) == set(one["delta"]), tree
        for leaf, (shape, shards) in leaves.items():
            assert sorted(d for d, _, _ in shards) == [0, 1, 2, 3]
            split = [i for i, n in enumerate(shape) if n % 4 == 0][:1]
            want = [n // 4 if i in split else n for i, n in enumerate(shape)]
            for _, got_shape, _ in shards:
                assert got_shape == want, (tree, leaf, shape, got_shape)
            if split:
                starts = sorted(start[split[0]] for _, _, start in shards)
                assert starts == [j * want[split[0]] for j in range(4)], \
                    (tree, leaf, starts)


def test_leaf_spec_splits_the_first_dimension_that_divides():
    assert reference.leaf_spec((12, 5), 4) == P("rows")
    assert reference.leaf_spec((5, 12), 4) == P(None, "rows")
    assert reference.leaf_spec((5, 7), 4) == P()
    assert reference.leaf_spec((5, 7), 1) == P("rows")


# ---------------------------------------------------------------------------
# described v5e 2x2: the Mistral-7B-v0.3 four-layer share fits
# ---------------------------------------------------------------------------

GiB = 2 ** 30
# the HBM one v5e chip gives a program: the compiler refuses a program
# with "Used X of 15.75G hbm" beyond it
V5E_HBM = 15.75 * GiB

# Mistral-7B-v0.3 as published (https://huggingface.co/mistralai/
# Mistral-7B-v0.3, config.json): d 4096, 32 query and 8 KV heads of 128,
# SwiGLU 14336, vocabulary 32,768, rope theta 1e6, RMSNorm eps 1e-5,
# untied head.  The share a four-chip cell holds: 4 of its 32 layers and
# an eighth of the vocabulary, 906,006,528 parameters.
MISTRAL_SHARE = dict(hidden_size=4096, intermediate_size=14336,
                     num_attention_heads=32, num_key_value_heads=8,
                     head_dim=128, num_hidden_layers=4, vocab_size=4096,
                     rms_norm_eps=1e-5, rope_theta=1e6,
                     tie_word_embeddings=False)
MISTRAL_PARAMS = 906_006_528
ROW = 8192          # tokens of one row, the cell's longest document


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache: keep it out of the cache
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        # an earlier test of this worker may have left a CPU mesh as the
        # global mesh: the described chips' arrays must not resolve there
        with jax.set_mesh(Mesh(np.empty((), dtype=object), ())):
            yield desc
        jax.config.update("jax_enable_compilation_cache", was)


def _footprint(compiled, resident):
    """Peak bytes on one chip: the call's arguments, the outputs that do
    not reuse a donated argument, its temporaries, and the state that
    stays resident beside it."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes + resident)


def test_mistral_share_fits_four_v5e_chips(topo):
    ref = reference.Reference(MISTRAL_SHARE, SimpleNamespace(context=ROW),
                              topo.devices)
    assert ref.n_dev == 4
    shapes = jax.eval_shape(
        lambda k: reference.init_weights(k, MISTRAL_SHARE),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == MISTRAL_PARAMS
    state = jax.tree.map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        shapes, ref.state_sharding)
    # every leaf of this share splits four ways: each chip holds a
    # quarter of each float32 copy of the state (3.62 GB whole)
    copy = 4 * MISTRAL_PARAMS // ref.n_dev
    rows = {k: jax.ShapeDtypeStruct((4, ROW), jnp.int32,
                                    sharding=ref.rows_sharding)
            for k in ("tokens", "labels", "seg", "pos")}
    weight = jax.ShapeDtypeStruct((4,), jnp.float32,
                                  sharding=ref.rows_sharding)
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    with jax.default_matmul_precision("highest"):
        grad = ref._grad.lower(state, rows, weight, scalar).compile()
        add = ref._add.lower(state, state).compile()
        adamw = ref._adamw.lower(state, state, state, state,
                                 scalar).compile()
    assert add.memory_analysis().argument_size_in_bytes == 2 * copy
    # resident beside each call: the gradient call leaves m, v and the
    # gradient sum; the add leaves w, m and v; AdamW takes the whole state
    for name, compiled, resident in (("grad", grad, 3 * copy),
                                     ("add", add, 3 * copy),
                                     ("adamw", adamw, 0)):
        used = _footprint(compiled, resident)
        assert used <= V5E_HBM, (name, used / GiB)
