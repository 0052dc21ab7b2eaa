"""Roofline machinery: the loop-aware HLO cost analyzer must multiply scan
bodies by trip count and attribute collectives correctly."""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.analysis import hlo_cost
from repro.analysis.roofline import Roofline, collective_bytes


SYNTH_HLO = """
HloModule test, num_partitions=4

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  %iv = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %one = s32[] constant(1)
  %iv2 = s32[] add(%iv, %one)
  %dot.1 = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8]{1,0} all-reduce(%dot.1), replica_groups={}, to_apply=%body
  ROOT %t = (s32[], f32[8,8]{1,0}) tuple(%iv2, %ar)
}

%cond (p2: (s32[], f32[8,8])) -> pred[] {
  %p2 = (s32[], f32[8,8]{1,0}) parameter(0)
  %iv3 = s32[] get-tuple-element(%p2), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%iv3, %n), direction=LT
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[8,8]{1,0}) tuple(%zero, %a)
  %w = (s32[], f32[8,8]{1,0}) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""


def test_while_trip_count_multiplies_flops():
    c = hlo_cost.analyze(SYNTH_HLO)
    # dot: 2*8*8*8 = 1024 flops, x5 trips = 5120 (+5 adds +5 compares)
    assert 5120 <= c.flops <= 5120 + 64


def test_collectives_scaled_by_trips():
    c = hlo_cost.analyze(SYNTH_HLO)
    assert c.coll["all-reduce"] == 5 * 8 * 8 * 4


def test_roofline_terms_and_bottleneck():
    r = Roofline(flops=197e12, hbm_bytes=819e9 / 2, coll_bytes=0.0,
                 coll_breakdown={}, model_flops=197e12 * 4, n_chips=4)
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(0.5)
    assert r.bottleneck == "compute"
    assert r.useful_flops_ratio == pytest.approx(1.0)
    assert r.roofline_fraction == pytest.approx(1.0)


def test_real_compiled_scan_costs():
    """Compile a tiny scan in a subprocess and verify flops scale with trip
    count (the XLA-cost-analysis bug this analyzer exists to fix)."""
    script = r"""
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from repro.analysis import hlo_cost
import json

def run(n):
    def f(xs, w):
        def body(c, x):
            return c + x @ w, None
        out, _ = jax.lax.scan(body, jnp.zeros((4, 8)), xs)
        return out
    c = jax.jit(f).lower(
        jax.ShapeDtypeStruct((n, 4, 8), jnp.float32),
        jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile()
    return hlo_cost.analyze(c.as_text()).flops

print(json.dumps({"f4": run(4), "f16": run(16)}))
"""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"    # a CPU rehearsal: never reach for a chip
    proc = subprocess.run([sys.executable, "-c", script,
                           os.path.abspath(src)],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    import json as j
    r = j.loads(proc.stdout.strip().splitlines()[-1])
    assert r["f16"] >= 3.5 * r["f4"]    # flops scale ~linearly with trips


def test_model_flops_formulas():
    from repro.analysis.model_flops import model_flops
    from repro.configs.registry import get_config
    cfg = get_config("qwen3-0.6b")
    t = model_flops(cfg, "train_step", "train_4k",
                    dict(global_batch=256, seq_len=4096))
    p = model_flops(cfg, "prefill", "prefill_32k",
                    dict(global_batch=32, seq_len=32768))
    d = model_flops(cfg, "serve_step", "decode_32k",
                    dict(global_batch=128, seq_len=32768))
    assert t > p > d > 0
    # train ~ 6*N*D at minimum
    n_tokens = 256 * 4096
    assert t >= 6 * cfg.n_active_params() * n_tokens
