"""Miniature dry-run: 8 forced host devices in a subprocess, smoke configs.

Validates the full lower->compile->analyze pipeline (sharding rules,
collective parsing) at CI scale; the real 512-device sweep runs via
``python -m repro.launch.dryrun --all``.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.models import (abstract_params, build_loss_fn, build_prefill_fn,
                          build_serve_step, input_specs)
from repro.models.config import ShapeSpec
from repro.models.api import _enc_len
from repro.models import init_decode_caches
from repro.launch.mesh import make_test_mesh
from repro.launch.sharding import (caches_shardings, inputs_shardings,
                                   params_shardings)
from repro.launch.dryrun import parse_collectives
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update

arch, kind, mesh_kind = sys.argv[1], sys.argv[2], sys.argv[3]
cfg = get_config(arch, smoke=True)
if mesh_kind == "multi":
    mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
else:
    mesh = make_test_mesh((2, 4), ("data", "model"))

spec = ShapeSpec("mini", seq_len=64, global_batch=8, kind=kind)
specs = input_specs(cfg, spec)
params = abstract_params(cfg)
pshard = params_shardings(params, mesh, fsdp=True)

if kind == "train":
    loss_fn = build_loss_fn(cfg)
    ocfg = AdamWConfig()
    ostate = jax.eval_shape(lambda p: adamw_init(p, ocfg), params)
    oshard = type(ostate)(step=NamedSharding(mesh, P()),
                          m=params_shardings(ostate.m, mesh, fsdp=True),
                          v=params_shardings(ostate.v, mesh, fsdp=True))
    def step(params, ostate, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        p2, s2 = adamw_update(grads, ostate, params, ocfg)
        return loss, p2, s2
    args = (params, ostate, specs)
    in_sh = (pshard, oshard, inputs_shardings(specs, mesh))
elif kind == "prefill":
    step = build_prefill_fn(cfg)
    args = (params, specs)
    in_sh = (pshard, inputs_shardings(specs, mesh))
else:
    serve = build_serve_step(cfg)
    step = lambda p, c, t, n: serve(p, c, t, n)
    args = (params, specs["caches"], specs["token"], specs["cache_len"])
    in_sh = (pshard, caches_shardings(specs["caches"], mesh),
             inputs_shardings(specs["token"], mesh),
             NamedSharding(mesh, P()))

lowered = jax.jit(step, in_shardings=in_sh).lower(*args)
compiled = lowered.compile()
ma = compiled.memory_analysis()
ca = compiled.cost_analysis()
coll = parse_collectives(compiled.as_text())
print(json.dumps({
    "flops": ca.get("flops", 0.0),
    "temp_bytes": ma.temp_size_in_bytes,
    "coll_bytes": coll["total_bytes"],
    "coll_counts": coll["counts"],
}))
"""


def _run(arch, kind, mesh_kind):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT, arch, kind, mesh_kind],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, f"{arch}/{kind}/{mesh_kind}:\n{out.stderr[-4000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("arch,kind", [
    ("qwen1_5_0_5b", "train"),
    ("granite_moe_3b_a800m", "train"),
    ("jamba_v0_1_52b", "train"),
    ("whisper_large_v3", "train"),
    ("llama_3_2_vision_90b", "prefill"),
    ("mamba2_130m", "decode"),
    ("llama4_maverick_400b_a17b", "decode"),
])
@pytest.mark.slow
def test_mini_dryrun_single(arch, kind):
    r = _run(arch, kind, "single")
    assert r["flops"] > 0
    # SPMD over a non-trivial mesh must produce collectives
    assert r["coll_bytes"] > 0, f"no collectives found: {r}"


@pytest.mark.parametrize("arch,kind", [
    ("qwen1_5_0_5b", "train"),
    ("mamba2_130m", "train"),
])
def test_mini_dryrun_multipod(arch, kind):
    r = _run(arch, kind, "multi")
    assert r["flops"] > 0
    assert r["coll_bytes"] > 0
