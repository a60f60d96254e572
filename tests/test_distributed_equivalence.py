"""Multi-device (8 fake CPUs) numerics equivalence for the perf-path
shardings: sequence-parallel attention, EP MoE in-model, full train step on
a mesh == single device."""
from conftest import run_with_devices


def test_seq_parallel_attention_matches_single_device():
    run_with_devices("""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.core import qat
from repro.launch.mesh import make_mesh
from repro.nn import transformer as T
from repro.nn.module import QuantCtx

cfg = get_config("smollm-360m").smoke()   # 4 heads % model-axis 4 == 0?  -> force reshard
cfg = dataclasses.replace(cfg, n_heads=3, n_kv=3, head_dim=16, d_model=48)
mesh = make_mesh((2, 4), ("data", "model"))
ctx = QuantCtx(quant=False, compute_dtype=jnp.float32)
key = jax.random.PRNGKey(0)
p = T.lm_init(key, cfg)
q = qat.build_qstate(p)
toks = jax.random.randint(key, (4, 16), 0, cfg.vocab)

ref, _, _ = T.lm_apply(p, q, toks, ctx, cfg, attn_reshard=False)
def f(p, toks):
    out, _, _ = T.lm_apply(p, q, toks, ctx, cfg, mesh=mesh, attn_reshard=True)
    return out
with mesh:
    out = jax.jit(f)(p, toks)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=1e-3)
print("seq-parallel attention == single-device OK")
""", n_devices=8)


def test_sharded_train_step_matches_single_device():
    run_with_devices("""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.launch import steps as S
from repro.launch.mesh import make_mesh
from repro.optim import adam, ec4t

cfg = get_config("smollm-360m").smoke()
mesh = make_mesh((2, 4), ("data", "model"))
key = jax.random.PRNGKey(0)
from repro.nn.transformer import lm_init
params = lm_init(key, cfg)
state = ec4t.init_train_state(params)
toks = jax.random.randint(key, (8, 16), 0, cfg.vocab)
batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}

# single-device reference
loss_fn1 = S._loss_fn(cfg, mesh=None, use_ep=False, remat="none")
step1 = ec4t.make_train_step(loss_fn1, adam.AdamConfig(lr=1e-3), lam=cfg.lam)
s1, m1 = jax.jit(step1)(state, batch)

# sharded
loss_fn2 = S._loss_fn(cfg, mesh=mesh, use_ep=True, remat="full")
step2 = ec4t.make_train_step(loss_fn2, adam.AdamConfig(lr=1e-3), lam=cfg.lam)
rules = S.make_rules(cfg, mesh)
p_specs = rules.param_specs(state["params"])
state_sh = {
    "params": jax.device_put(state["params"], rules.named(mesh, p_specs)),
    "opt": state["opt"], "qstate": state["qstate"],
}
with mesh:
    s2, m2 = jax.jit(step2)(state_sh, batch)
np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-4)
for l1, l2 in zip(jax.tree_util.tree_leaves(s1["params"]),
                  jax.tree_util.tree_leaves(s2["params"])):
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               atol=5e-3, rtol=5e-3)
print("sharded train step == single-device OK, loss", float(m2["loss"]))
""", n_devices=8)


def test_mini_dryrun_all_families_compile():
    """CI-speed dry-run: one small cell per family on a (2,4) mesh."""
    run_with_devices("""
import jax
from repro.configs import get_config
from repro.launch import steps as S, specs
from repro.launch.mesh import make_mesh

for shape in specs.SHAPES.values():
    pass
specs.SHAPES["train_4k"] = dict(specs.SHAPES["train_4k"], seq=64, batch=8)
specs.SHAPES["prefill_32k"] = dict(specs.SHAPES["prefill_32k"], seq=64, batch=8)
specs.SHAPES["decode_32k"] = dict(specs.SHAPES["decode_32k"], seq=64, batch=8)
mesh = make_mesh((2, 4), ("data", "model"))
for arch in ("smollm-360m", "deepseek-v3-671b", "grok-1-314b",
             "mamba2-1.3b", "hymba-1.5b", "whisper-base"):
    cfg = get_config(arch).smoke()
    for shape in ("train_4k", "prefill_32k", "decode_32k"):
        bundle = S.build_step(cfg, mesh, shape)
        with mesh:
            compiled = jax.jit(
                bundle.fn, in_shardings=bundle.in_shardings,
                out_shardings=bundle.out_shardings,
                donate_argnums=bundle.donate).lower(*bundle.args).compile()
        assert compiled.cost_analysis() is not None
    print(arch, "OK")
""", n_devices=8, timeout=1200)
