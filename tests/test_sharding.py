"""Distribution-layer tests.

Rule-level checks run in-process (PartitionSpec construction only); the
lower+compile integration check runs in a subprocess so the 8 fake XLA
host devices never leak into the main test process (tests must see 1
device, per the dry-run contract).
"""

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_param_specs_divisibility_rules():
    """Every spec only shards dims that divide the axis size."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.configs import ARCH_IDS, get_config
        from repro.distributed.sharding import ShardingRules, axis_size
        from repro.models import build_model

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            model = build_model(cfg)
            shapes = model.param_shapes()
            for mode in ("train", "serve"):
                rules = ShardingRules(cfg, mesh, mode=mode)
                specs = rules.param_specs(shapes)
                flat_shapes = jax.tree_util.tree_leaves(shapes)
                flat_specs = jax.tree_util.tree_leaves(
                    specs, is_leaf=lambda x: isinstance(x, P))
                assert len(flat_shapes) == len(flat_specs)
                for sh, sp in zip(flat_shapes, flat_specs):
                    assert len(sp) <= len(sh.shape)
                    for dim, ax in zip(sh.shape, sp):
                        if ax is None:
                            continue
                        n = axis_size(mesh, ax)
                        assert dim % n == 0, (arch, mode, sh.shape, sp)
        print("RULES-OK")
    """)
    out = subprocess.run([sys.executable, "-c", code],
                         env={**os.environ, "PYTHONPATH": SRC},
                         capture_output=True, text=True, timeout=560)
    assert "RULES-OK" in out.stdout, out.stdout + out.stderr


def test_smoke_config_lowers_and_compiles_on_mini_mesh():
    """End-to-end dry-run (train + decode) on a 2x4 fake-device mesh."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from repro.launch.dryrun import lower_combo
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        for arch, shape in [("qwen3-0.6b", "train_4k"),
                            ("granite-moe-1b-a400m", "prefill_32k"),
                            ("mamba2-130m", "decode_32k"),
                            ("recurrentgemma-9b", "long_500k")]:
            rec = lower_combo(arch, shape, mesh=mesh)
            assert "error" not in rec, rec
            assert rec["flops_per_device_raw"] > 0
        print("LOWER-OK")
    """)
    out = subprocess.run([sys.executable, "-c", code],
                         env={**os.environ, "PYTHONPATH": SRC},
                         capture_output=True, text=True, timeout=560)
    assert "LOWER-OK" in out.stdout, out.stdout[-2000:] + out.stderr[-3000:]


def test_collective_parser():
    from repro.launch.dryrun import collective_bytes
    hlo = """
  %all-gather = f32[64,256]{0,1} all-gather(%copy), channel_id=1, replica_groups=[2,4]<=[8], metadata={op_name="jit(f)/x"}
  %all-reduce.1 = f32[128]{0} all-reduce(%p), replica_groups=[2,4]<=[8], metadata={op_name="jit(f)/while/body/y"}
  %other = f32[8]{0} add(%a, %b)
"""
    out = collective_bytes(hlo, loop_trip_counts=[10], total_devices=8)
    # all-gather: 64*256*4 bytes * 3/4
    assert out["all-gather"] == 64 * 256 * 4 * 3 / 4
    # all-reduce inside loop: 2 * 128*4 * 3/4 * 10 trips
    assert out["all-reduce"] == 2 * 128 * 4 * 3 / 4 * 10
    assert out["total"] == out["all-gather"] + out["all-reduce"]


def test_mesh_constants():
    from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16
    assert PEAK_FLOPS_BF16 == 197e12 and HBM_BW == 819e9 and ICI_BW == 50e9
