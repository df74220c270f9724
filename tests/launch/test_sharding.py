"""Sharding rules: every param/cache spec must be valid for the mesh."""
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ARCH_IDS, get_smoke_config
from repro.models.lm import LM, QuantConfig
from repro.launch.specs import shapes_of_init
from repro.parallel import sharding as SH


def fake_mesh(shape, axes):
    """Abstract mesh is enough to validate spec construction."""
    n = int(np.prod(shape))
    devs = jax.devices() * n
    return Mesh(np.array(devs[:n]).reshape(shape), axes)


@pytest.mark.parametrize("arch", ARCH_IDS[:6])
@pytest.mark.parametrize("rules_name", ["train", "serve"])
def test_param_specs_divide_dims(arch, rules_name):
    cfg = get_smoke_config(arch)
    lm = LM(cfg)
    params, axes = shapes_of_init(lm)
    mesh = fake_mesh((2, 2), ("data", "model"))
    rules = SH.TRAIN_RULES if rules_name == "train" else SH.SERVE_RULES
    specs = SH.tree_pspecs(axes, params, mesh, rules)

    def check(p, s):
        assert isinstance(s, P)
        for dim, ax in zip(p.shape, tuple(s) + (None,) * p.ndim):
            if ax is not None:
                size = mesh.shape[ax] if isinstance(ax, str) else int(
                    np.prod([mesh.shape[a] for a in ax]))
                assert dim % size == 0, (p.shape, s)

    jax.tree.map(check, params, specs,
                 is_leaf=lambda x: isinstance(x, P))


@pytest.mark.parametrize("arch", ["qwen2_72b", "zamba2_2p7b", "rwkv6_1p6b"])
def test_cache_specs_valid(arch):
    cfg = get_smoke_config(arch)
    lm = LM(cfg, quant=QuantConfig(impl="ref"))
    cache = jax.eval_shape(lambda: lm.init_cache(8, 64))
    mesh = fake_mesh((2, 2), ("data", "model"))
    specs = SH.cache_pspecs(cache, mesh)

    def check(p, s):
        for dim, ax in zip(p.shape, tuple(s) + (None,) * p.ndim):
            if ax is not None:
                size = mesh.shape[ax] if isinstance(ax, str) else int(
                    np.prod([mesh.shape[a] for a in ax]))
                assert dim % size == 0, (p.shape, s)

    jax.tree.map(check, cache, specs, is_leaf=lambda x: isinstance(x, P))


def test_spec_for_axes_divisibility_fallback():
    """Any dim not divisible by its mesh axis falls back to replicated
    for THAT dim only — never a lowering failure, never contaminating
    the dims that do divide."""
    mesh = fake_mesh((2, 2), ("data", "model"))
    spec = SH.spec_for_axes(("heads", "mlp"), (5, 8), mesh,
                            SH.SERVE_RULES)     # 5 % 2 != 0 on "model"
    assert spec == P(None, "model")            # heads dim fell back; the
    #                                            "model" axis is then free
    #                                            for the dividing mlp dim
    # fully divisible → both rules resolve
    spec2 = SH.spec_for_axes(("embed", "heads"), (4, 8), mesh,
                             SH.TRAIN_RULES)
    assert spec2 == P("data", "model")
    # an axis already used by an earlier dim is never repeated
    spec3 = SH.spec_for_axes(("heads", "mlp"), (4, 8), mesh,
                             SH.SERVE_RULES)
    assert spec3 == P("model", None)
    # unknown logical names and rules mapping to absent mesh axes → None
    spec4 = SH.spec_for_axes(("nonsense", "vocab"), (4, 8),
                             fake_mesh((4,), ("data",)), SH.SERVE_RULES)
    assert spec4 == P(None, None)


def test_maybe_shard_off_mesh_is_identity():
    """Layers call maybe_shard unconditionally; with no ambient mesh it
    must be a no-op returning the SAME array uncommitted."""
    x = jax.numpy.arange(8.0)
    y = SH.maybe_shard(x, "model")
    assert y is x
    # under an ambient mesh it applies the constraint (divisible dim)
    with fake_mesh((1,), ("model",)):
        z = SH.maybe_shard(x, "model")
        np.testing.assert_array_equal(np.asarray(z), np.asarray(x))


def test_cache_pspecs_paged_pool_layout():
    """The serving engine's live pools: ONLY the kv-head dim shards
    (dim 2 of [L, P, Hkv, ps, D/2]) — pages are a host-global namespace
    — and the static per-channel scales [Hkv, 1, D] shard to match.
    Head counts not dividing the model axis fall back to replicated."""
    mesh = fake_mesh((2, 2), ("data", "model"))
    cache = {
        "k_pool": np.zeros((2, 16, 2, 8, 16), np.uint8),
        "v_pool": np.zeros((2, 16, 2, 8, 16), np.uint8),
        "k_scale": np.zeros((2, 1, 32), np.float32),
        "k_zero": np.zeros((2, 1, 32), np.float32),
        "v_scale": np.zeros((2, 1, 32), np.float32),
        "v_zero": np.zeros((2, 1, 32), np.float32),
    }
    specs = SH.cache_pspecs(cache, mesh)
    assert specs["k_pool"] == P(None, None, "model", None, None)
    assert specs["v_pool"] == P(None, None, "model", None, None)
    for name in ("k_scale", "k_zero", "v_scale", "v_zero"):
        assert specs[name] == P("model", None, None)
    # 3 kv heads on a 2-wide model axis → whole pool replicated
    odd = {"k_pool": np.zeros((2, 16, 3, 8, 16), np.uint8),
           "k_scale": np.zeros((3, 1, 32), np.float32)}
    specs_odd = SH.cache_pspecs(odd, mesh)
    assert specs_odd["k_pool"] == P(None, None, None, None, None)
    assert specs_odd["k_scale"] == P(None, None, None)


def test_batch_spec_pod_axis():
    mesh3 = fake_mesh((2, 2, 2), ("pod", "data", "model"))
    assert SH.batch_spec(mesh3) == P(("pod", "data"))
    mesh2 = fake_mesh((2, 2), ("data", "model"))
    assert SH.batch_spec(mesh2) == P("data")


def test_make_local_mesh_refuses_silent_clamp():
    """Asking for more devices than exist must raise by default — a
    silently clamped mesh serves a different topology than requested
    (--mesh 2x4 on one device would quietly run 1x1)."""
    from repro.launch.mesh import make_local_mesh
    n = len(jax.devices())
    with pytest.raises(ValueError, match="devices"):
        make_local_mesh(n + 1, 1)
    with pytest.raises(ValueError, match="devices"):
        make_local_mesh(2, n)               # 2*n > n for any n >= 1
    # a fitting request is honored exactly
    mesh = make_local_mesh(1, 1)
    assert (int(mesh.shape["data"]), int(mesh.shape["model"])) == (1, 1)


def test_make_local_mesh_allow_shrink_warns_with_effective_mesh():
    """allow_shrink=True restores the best-effort clamp, but loudly: a
    UserWarning names the effective mesh actually built."""
    from repro.launch.mesh import make_local_mesh
    n = len(jax.devices())
    with pytest.warns(UserWarning, match="effective mesh"):
        mesh = make_local_mesh(n + 1, n + 1, allow_shrink=True)
    assert int(mesh.shape["data"]) * int(mesh.shape["model"]) <= n


def test_make_replica_meshes_disjoint_slices():
    """Per-replica meshes carve disjoint device slices (data axis as N
    independent engines) and refuse to oversubscribe."""
    from repro.launch.mesh import make_replica_meshes
    n = len(jax.devices())
    meshes = make_replica_meshes(n, model=1)
    assert len(meshes) == n
    seen = set()
    for m in meshes:
        assert (int(m.shape["data"]), int(m.shape["model"])) == (1, 1)
        ids = {d.id for d in m.devices.flat}
        assert not ids & seen               # disjoint
        seen |= ids
    with pytest.raises(ValueError, match="devices"):
        make_replica_meshes(n + 1, model=1)


def test_dryrun_smoke_subprocess():
    """Lower+compile one smoke cell on 8 fake devices in a subprocess
    (isolates the XLA device-count env from this process)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import warnings; warnings.filterwarnings("ignore")
import numpy as np, jax
from jax.sharding import Mesh
import repro.configs.base as CB
CB.get_config = CB.get_smoke_config
CB.SHAPES = {"train_4k": CB.ShapeConfig("train_4k", 64, 8, "train"),
             "decode_32k": CB.ShapeConfig("decode_32k", 128, 8, "decode")}
import repro.launch.specs as SP
SP.SHAPES = CB.SHAPES
mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
for shape in ("train_4k", "decode_32k"):
    cell = SP.build_cell("llama3_8b", shape, mesh)
    with mesh:
        c = jax.jit(cell.step_fn, in_shardings=cell.in_shardings).lower(
            *cell.args).compile()
    assert SP.cost_analysis_dict(c).get("flops", 0) >= 0
print("OK")
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert "OK" in out.stdout, out.stderr[-2000:]
