"""Roofline HLO parser unit tests + optimizer sanity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim.optimizers import Adafactor, AdamW
from repro.roofline.hlo import (
    CollectiveStats,
    parse_collectives,
    roofline_terms,
)

jax.config.update("jax_platform_name", "cpu")

HLO = """
HloModule test
  %x1 = f32[1024,512]{1,0} all-reduce(f32[1024,512]{1,0} %p0), replica_groups=[16,16]<=[256], to_apply=%add
  %x2 = bf16[256,128]{1,0} all-gather(bf16[16,128]{1,0} %p1), replica_groups=[2,8]<=[16], dimensions={0}
  %x3 = f32[64]{0} reduce-scatter(f32[512]{0} %p2), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %x4 = (f32[32,32]{1,0}, f32[32,32]{1,0}) all-to-all(f32[32,32]{1,0} %a, f32[32,32]{1,0} %b), replica_groups=[4,2]<=[8]
  %x5 = f32[128]{0} collective-permute(f32[128]{0} %p3), source_target_pairs={{0,1}}
  %y = f32[10]{0} add(f32[10]{0} %a, f32[10]{0} %b)
"""


def test_parse_collectives_kinds_and_groups():
    st = parse_collectives(HLO)
    kinds = [op["kind"] for op in st.ops]
    assert kinds == ["all-reduce", "all-gather", "reduce-scatter",
                     "all-to-all", "collective-permute"]
    groups = [op["group"] for op in st.ops]
    assert groups == [16, 8, 8, 2, 1]


def test_parse_collectives_byte_accounting():
    st = parse_collectives(HLO)
    ar = st.ops[0]
    assert ar["bytes"] == 1024 * 512 * 4
    assert ar["wire_bytes"] == int(2 * ar["bytes"] * 15 / 16)
    ag = st.ops[1]
    assert ag["bytes"] == 256 * 128 * 2
    assert ag["operand_bytes"] == ag["bytes"] // 8
    rs = st.ops[2]
    assert rs["operand_bytes"] == 512 * 4   # per-device input is the full array
    a2a = st.ops[3]
    assert a2a["bytes"] == 2 * 32 * 32 * 4  # tuple shape


def test_roofline_terms_bottleneck():
    v5e = "TPU v5 lite"
    t = roofline_terms(197e12, 100e9, 1e9, v5e)   # 1s compute, tiny others
    assert t["bottleneck"] == "compute"
    assert abs(t["compute_s"] - 1.0) < 1e-9
    t = roofline_terms(1e9, 819e9, 1e9, v5e)      # 1s memory
    assert t["bottleneck"] == "memory"
    t = roofline_terms(1e9, 1e9, 50e9, v5e)       # 1s collective
    assert t["bottleneck"] == "collective"
    assert t["compute_fraction_of_bound"] < 0.01


def test_roofline_peaks_refuse_unknown_device_kind():
    with pytest.raises(ValueError, match="no peaks for device kind"):
        roofline_terms(1e9, 1e9, 1e9, "cpu")


def _quadratic_problem():
    key = jax.random.PRNGKey(0)
    A = jax.random.normal(key, (20, 10)) / 5.0
    b = jax.random.normal(jax.random.PRNGKey(1), (20,))
    params = {"w": jnp.zeros((10, 4)), "b": jnp.zeros((4,))}

    def loss(p):
        pred = A @ p["w"] + p["b"]
        return jnp.mean((pred - b[:, None]) ** 2)

    return params, loss


@pytest.mark.parametrize("opt", [
    AdamW(lr=0.05, warmup_steps=0, total_steps=400, weight_decay=0.0),
    Adafactor(lr=0.5, warmup_steps=0, total_steps=400),
])
def test_optimizer_decreases_quadratic(opt):
    params, loss = _quadratic_problem()
    # analytic optimum of the (overdetermined) least-squares problem
    import numpy as np
    key = jax.random.PRNGKey(0)
    A = np.asarray(jax.random.normal(key, (20, 10)) / 5.0)
    b = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (20,)))
    A1 = np.concatenate([A, np.ones((20, 1))], axis=1)
    w, *_ = np.linalg.lstsq(A1, b, rcond=None)
    l_star = float(np.mean((A1 @ w - b) ** 2))
    state = opt.init(params)
    l0 = float(loss(params))
    for i in range(200):
        g = jax.grad(loss)(params)
        params, state = opt.update(g, state, params,
                                   jnp.asarray(i, jnp.float32))
    l_end = float(loss(params))
    # Adafactor (no momentum, RMS-clipped steps) converges slower on this
    # anisotropic quadratic — looser gate.
    frac = 0.25 if isinstance(opt, AdamW) else 0.55
    assert l_end < l_star + frac * (l0 - l_star), (l_end, l_star, l0)


def test_adafactor_state_is_factored():
    params = {"w": jnp.zeros((64, 32)), "stack": jnp.zeros((4, 16, 8)),
              "b": jnp.zeros((7,))}
    st = Adafactor().init(params)
    assert st["f"]["w"]["vr"].shape == (64,)
    assert st["f"]["w"]["vc"].shape == (32,)
    assert st["f"]["stack"]["vr"].shape == (4, 16)
    assert st["f"]["stack"]["vc"].shape == (4, 8)
    assert st["f"]["b"]["v"].shape == (7,)
    n_state = sum(x.size for x in jax.tree.leaves(st))
    n_param = sum(x.size for x in jax.tree.leaves(params))
    assert n_state < 0.2 * n_param  # the arctic-480b memory plan


def test_adamw_weight_decay_shrinks():
    opt = AdamW(lr=0.1, weight_decay=0.5, warmup_steps=0, total_steps=10)
    params = {"w": jnp.ones((4,))}
    state = opt.init(params)
    g = {"w": jnp.zeros((4,))}
    p2, _ = opt.update(g, state, params, jnp.asarray(5, jnp.float32))
    assert float(p2["w"][0]) < 1.0
