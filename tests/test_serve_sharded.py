"""Acceptance: a quantized + sharded tree serves under a 2-device mesh.

The main test process must keep seeing exactly 1 CPU device (see
conftest), so this runs in a subprocess with
``--xla_force_host_platform_device_count=2`` — the same trick
``launch/dryrun.py`` uses.  The child builds the smoke llama, applies
branched + SVD surgery (mixed tree), quantizes int8 *with the axes
rewrite*, resolves every leaf through ``make_param_shardings`` on a
``(1, 2)`` mesh (any unresolvable ``*_q``/``*_scale`` key raises —
"no key-resolution failures"), places the params, and serves
end-to-end.
"""
import os
import subprocess
import sys

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.configs.base import LRDConfig, ParallelConfig, RunConfig
from repro.core.surgery import decompose_model
from repro.launch.mesh import make_mesh
from repro.models.api import get_model
from repro.parallel import sharding as shd
from repro.quant import quantize_tree
from repro.serve.engine import Request, ServeEngine

assert len(jax.devices()) == 2, jax.devices()
mesh = make_mesh((1, 2), ("data", "model"))

cfg = registry.get("llama3.2-1b").smoke
# branches=2 with a small align so some layers branch and the rest take
# SVD pairs -> a mixed branched + SVD tree, per the acceptance criteria.
lrd = LRDConfig(enabled=True, rank_mode="ratio", min_dim=32, branches=2,
                rank_align=8)
run = RunConfig(model=cfg, lrd=lrd, parallel=ParallelConfig())
m = get_model(cfg)
params, axes = m.init(jax.random.PRNGKey(0))
params, axes, report = decompose_model(params, axes, lrd)
kinds = {d.kind for d in report.decisions}
assert "branched" in kinds and "svd" in kinds, kinds

# Quantize AFTER the axes were built (the old failure mode), with the
# plan-level axes rewrite.
params, axes = quantize_tree(params, "int8", axes=axes)

# Every leaf must resolve -- k_q inherits k's axes, k_scale the out dim.
shardings = shd.make_param_shardings(mesh, params, axes, run.parallel)
params = jax.tree.map(lambda x, s: jax.device_put(x, s), params, shardings)

eng = ServeEngine(run, params, slots=2, max_seq=64)
assert eng.plan_summary["quantized"] > 0, eng.plan_summary
assert eng.plan_summary["by_kind"].get("branched"), eng.plan_summary
reqs = [Request(uid=i, prompt=[i + 1, 2, 3], max_new_tokens=4)
        for i in range(3)]
for r in reqs:
    eng.add_request(r)
done = eng.run_until_done()
assert {r.uid for r in done} == {0, 1, 2}
assert all(r.done and len(r.output) == 4 for r in reqs)
print("OK", eng.plan_summary["by_kind"])
"""


def test_quantized_sharded_tree_serves_on_2dev_mesh():
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(root, "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    assert "OK" in proc.stdout
