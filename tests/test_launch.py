"""Launch-layer tests: sharding rules (divisibility fallbacks, presets),
input specs for all 40 cells, and an end-to-end lower+compile of a reduced
config on a small multi-device mesh (subprocess)."""
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import all_arch_ids, get_config, get_reduced
from repro.launch.mesh import abstract_mesh, make_mesh
from repro.launch.specs import SHAPES, input_specs, shape_applicable


class TestInputSpecs:
    @pytest.mark.parametrize("arch", all_arch_ids())
    @pytest.mark.parametrize("shape", list(SHAPES))
    def test_every_cell_has_specs(self, arch, shape):
        cfg = get_config(arch)
        ok, why = shape_applicable(cfg, shape)
        if not ok:
            assert cfg.sub_quadratic is False and shape == "long_500k"
            return
        spec = input_specs(cfg, shape)
        inputs = spec["inputs"]
        if cfg.modality_stub:
            assert "embeds" in inputs and "tokens" not in inputs
            assert inputs["embeds"].shape[-1] == cfg.d_model
        else:
            assert "tokens" in inputs
        if cfg.rope_kind == "mrope":
            assert inputs["positions"].shape[0] == 3
        if SHAPES[shape]["kind"] == "train":
            assert "targets" in inputs

    def test_long_500k_only_subquadratic(self):
        runs = [a for a in all_arch_ids()
                if shape_applicable(get_config(a), "long_500k")[0]]
        assert sorted(runs) == ["xlstm-350m", "zamba2-1.2b"]


class TestShardingRules:
    def _mesh(self):
        return make_mesh((1, 1), ("data", "model"))

    def test_param_specs_cover_tree(self):
        from repro.launch.sharding import param_pspecs
        from repro.models import Model

        for arch in ["glm4-9b", "granite-moe-1b-a400m", "zamba2-1.2b",
                     "xlstm-350m"]:
            cfg = get_reduced(arch)
            model = Model(cfg)
            shapes = jax.eval_shape(lambda m=model: m.init(0))
            specs = param_pspecs(shapes, cfg, self._mesh())
            ns = len(jax.tree.leaves(shapes))
            npec = len(jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(x, P)))
            assert ns == npec, f"{arch}: {ns} leaves vs {npec} specs"

    def test_divisibility_fallback(self):
        """A dim not divisible by its axis must fall back to replication."""
        from repro.launch.sharding import _resolve

        mesh = abstract_mesh((4, 16), ("data", "model"))
        spec = _resolve(("F", "M"), (100, 49155), mesh, True, True)
        assert spec[1] is None  # 49155 % 16 != 0 -> replicate
        assert spec[0] == "data"  # 100 % 4 == 0 -> FSDP ok
        spec = _resolve(("F", "M"), (101, 512), mesh, True, True)
        assert spec == P(None, "model")  # 101 % 4 != 0 -> no FSDP

    def test_pure_dp_preset_replicates_but_keeps_ep(self):
        from repro.launch.sharding import param_pspecs
        from repro.models import Model

        cfg = get_reduced("granite-moe-1b-a400m")
        mesh = abstract_mesh((1, 4), ("data", "model"))
        model = Model(cfg, mesh=mesh)
        shapes = jax.eval_shape(lambda: model.init(0))
        specs = param_pspecs(shapes, model.cfg, mesh, tp=False)
        # attention weights replicated over model...
        attn_spec = specs["blocks"]["attn"]["wq"]
        assert "model" not in [a for a in attn_spec if a]
        # ...but expert tables stay on the EP axis
        moe_spec = specs["blocks"]["moe"]["w_in"]
        assert "model" in [a for a in jax.tree.leaves(
            moe_spec, is_leaf=lambda x: x is not None) if isinstance(a, str)] \
            or moe_spec[1] == "model" or moe_spec == P(None, "model", None) \
            or "model" in tuple(moe_spec)


@pytest.mark.slow
def test_reduced_config_compiles_on_small_mesh():
    """build_train_step lowers + compiles a reduced MoE config on a 2×4
    mesh — the dry-run machinery end-to-end, at test scale."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import jax, dataclasses
        from repro.configs import get_reduced
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import build_train_step
        from repro.launch.hlo import parse_collectives
        import repro.launch.specs as specs_mod
        # shrink the workload shape for test scale
        specs_mod.SHAPES["train_4k"] = dict(seq=64, batch=8, kind="train")
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_reduced("granite-moe-1b-a400m")
        step = build_train_step(cfg, mesh, "train_4k", grad_accum=1)
        compiled = step.fn.lower(*step.arg_specs).compile()
        assert compiled.cost_analysis().get("flops", 0) > 0
        colls = parse_collectives(compiled.as_text())
        assert colls.count > 0  # EP all_to_all / psum must be present
        print("OK", int(colls.count))
    """)
    out = subprocess.run([sys.executable, "-c", code], cwd="/root/repo",
                         capture_output=True, text=True, timeout=600)
    assert "OK" in out.stdout, out.stderr[-2000:]
