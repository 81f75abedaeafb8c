"""Small-mesh dry-run coherence: every (arch × shape-kind) lowers + compiles
on an 8-device host mesh with the same code path as the 512-device run.

Runs in a subprocess because the device count must be fixed before jax
initializes (the main test process keeps 1 CPU device).
"""
import json
import os
import subprocess
import sys

import pytest

from repro.configs import ARCH_NAMES

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, {src!r})
import jax
import jax.numpy as jnp
from repro.configs import get_reduced
from repro.configs.shapes import Shape
from repro.dist import sharding as shd
from repro.launch import specs as SP
from repro.models.model import Model
from repro.train.optimizer import AdamW, AdamWConfig
from repro.train.serve import make_decode_step
from repro.train.step import make_train_step

arch = sys.argv[1]
cfg = get_reduced(arch)
mesh = shd.make_mesh((4, 2), ("data", "model"))
rules = shd.make_rules(mesh)
model = Model(cfg)
results = {{}}

pspec = SP.params_specs(cfg)
p_sh = shd.tree_shardings(pspec.args, pspec.axes, mesh, rules)

with mesh, shd.activation_sharding(mesh, rules):
    # train cell
    shape = Shape("t", "train", 32, 8)
    bspec = SP.batch_specs(cfg, shape, with_labels=True)
    b_sh = shd.tree_shardings(bspec.args, bspec.axes, mesh, rules)
    opt = AdamW(AdamWConfig())
    opt_shapes = jax.eval_shape(opt.init, pspec.args)
    o_sh = shd.tree_shardings(opt_shapes, opt.state_axes(pspec.axes), mesh, rules)
    step = make_train_step(model, opt, microbatches=2)
    c = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                out_shardings=(p_sh, o_sh, None)).lower(
        pspec.args, opt_shapes, bspec.args).compile()
    results["train"] = c.memory_analysis().temp_size_in_bytes

    # decode cell
    shape = Shape("d", "decode", 64, 8)
    dsp = SP.decode_specs(cfg, shape)
    c_sh = shd.tree_shardings(dsp["cache"].args, dsp["cache"].axes, mesh, rules)
    t_sh = shd.sharding_for(dsp["token"].args.shape, dsp["token"].axes, mesh, rules)
    decode = make_decode_step(model)
    def serve_step(params, cache, token, pos):
        nxt, cache, _ = decode(params, cache, token, pos)
        return nxt, cache
    c = jax.jit(serve_step, in_shardings=(p_sh, c_sh, t_sh, None),
                out_shardings=(t_sh, c_sh)).lower(
        pspec.args, dsp["cache"].args, dsp["token"].args, dsp["pos"].args
    ).compile()
    results["decode"] = c.memory_analysis().temp_size_in_bytes

print("RESULT " + json.dumps(results))
"""


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_small_mesh_lowering(arch, tmp_path):
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    script = _SCRIPT.format(src=src)
    proc = subprocess.run([sys.executable, "-c", script, arch],
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    assert line, proc.stdout
    results = json.loads(line[0][len("RESULT "):])
    assert set(results) == {"train", "decode"}
