"""The FLOP and byte counts: by hand for MiniCPM-2B at one layer, per
kernel call from the HLO text a TPU trace names it by, and against XLA's
own count of an f32 training step at a toy size."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import pytest

import tiny
import sys
sys.path.insert(0, str(tiny.ONCHIP / "configs"))
import counts
import peaks

MINICPM = json.loads((tiny.ONCHIP / "configs" / "minicpm-2b.json").read_text())
SIZES = tiny.harness.load_module(tiny.ONCHIP / "configs"
                                 / "dense_decoder.py").sizes(MINICPM)
JOB = {"batch": 2, "seq": 1024}


def test_minicpm_one_layer_by_hand():
    s = dict(SIZES, n_layers=1)
    d, ff, v = 2304, 5760, 122753
    layer = 4 * d * d + 3 * d * ff          # q, k, v, o (MHA) and the MLP
    assert layer == 61_046_784
    assert counts.matmul_weights(s) == layer + d * v == 343_869_696
    attn = 6 * 36 * 64 * 1024               # score and value, causal half
    assert counts.model_flops_per_token(s, JOB) == 6 * 343_869_696 + attn


def _gemm_op(out, a, b):
    return (f"%s2fp8_matmul_pallas.7 = f32[{out}]{{1,0:T(8,128)}} "
            f"custom-call(f32[1,1]{{1,0:T(1,128)}} %s.1, f8e5m2[{a}]"
            f"{{1,0:T(8,128)(4,1)}} %pad.1, f8e5m2[{b}]{{1,0}} %pad.2), "
            f"custom_call_target=\"tpu_custom_call\"")


# every payload GEMM signature in a TPU v5e trace of the MiniCPM cell:
# output, A, B (padded as the kernel pads them), and the published M, K, N
MINICPM_GEMMS = [
    ("2048,122880", "2048,2560", "2560,122880", (2048, 2304, 122753)),
    ("2048,2304", "2048,122880", "2304,122880", (2048, 122753, 2304)),
    ("2304,122880", "2048,2304", "2048,122880", (2304, 2048, 122753)),
    ("2048,2304", "2048,2560", "2304,2560", (2048, 2304, 2304)),
    ("2048,2304", "2048,2560", "2560,2304", (2048, 2304, 2304)),
    ("2304,2304", "2048,2304", "2048,2304", (2304, 2048, 2304)),
    ("2048,2304", "2048,6144", "2304,6144", (2048, 5760, 2304)),
    ("2048,2304", "2048,6144", "6144,2304", (2048, 5760, 2304)),
    ("2048,5888", "2048,2560", "2560,5888", (2048, 2304, 5760)),
    ("2048,5888", "2048,2560", "5888,2560", (2048, 2304, 5760)),
    ("2304,5888", "2048,2304", "2048,5888", (2304, 2048, 5760)),
    ("5888,2304", "2048,5888", "2048,2304", (5760, 2048, 2304)),
]


@pytest.mark.parametrize("out,a,b,mkn", MINICPM_GEMMS)
def test_gemm_call_reads_the_published_shape(out, a, b, mkn):
    m, k, n = mkn
    call = counts.gemm_call(_gemm_op(out, a, b), SIZES, JOB, count=3)
    assert call == counts.Call(f"gemm[1,{m},{k},{n}]", 2.0 * m * k * n,
                               m * k + k * n + 4 * m * n, 3)


def test_gemm_call_refuses_a_shape_the_step_does_not_have():
    with pytest.raises(ValueError):
        counts.gemm_call(_gemm_op("2048,4096", "2048,4096", "4096,4096"),
                         SIZES, JOB)


def _qflash_op(outs, n_payloads=3, rows=0, kv_rows=72):
    q, kv = "f8e5m2[72,1024,128]{2,1,0} %q", f"f8e5m2[{kv_rows},1024,128] %k"
    payloads = [q, kv, kv, q][:n_payloads]
    ins = ", ".join(["f32[1,1]{1,0} %s"] + payloads
                    + ["f32[72,1024,1]{2,1,0} %r"] * rows)
    return (f"%checkpoint.3 = {outs} custom-call({ins}), "
            f"custom_call_target=\"tpu_custom_call\"")


def test_qflash_calls_by_their_outputs():
    pairs = 2 * 36 * 1024 * 1024 / 2       # causal (query, key) pairs
    elems = 2 * 36 * 1024 * 64             # at the published head dim 64
    row = 2 * 36 * 1024 * 4
    fwd = counts.qflash_call(_qflash_op(
        "(f32[72,1024,128]{2,1,0}, f32[72,1024,1]{2,1,0})"), SIZES, JOB)
    assert fwd == counts.Call("qflash.fwd", 4 * pairs * 64,
                              3 * elems + 4 * elems + row, 1)
    dq = counts.qflash_call(_qflash_op("f32[72,1024,128]{2,1,0}", 4, 2),
                            SIZES, JOB)
    assert dq.name == "qflash.dq" and dq.flops == 6 * pairs * 64
    dkdv = counts.qflash_call(_qflash_op(
        "(f32[72,1024,128]{2,1,0}, f32[72,1024,128]{2,1,0})", 4, 2),
        SIZES, JOB)
    assert dkdv.name == "qflash.dkdv" and dkdv.flops == 8 * pairs * 64


def test_qflash_under_grouped_query_attention():
    """K and V at a quarter of Q's heads (GQA 4:1, as StableLM's): the
    call is still told by its operands, and counted at Q's heads with K
    and V's bytes at theirs."""
    gqa = dict(SIZES, kv_heads=9)
    op = _qflash_op("(f32[72,1024,128]{2,1,0}, f32[72,1024,1]{2,1,0})",
                    kv_rows=18)
    reader = tiny.harness.reader("qflash_roofline_pct")
    assert reader.is_qflash(op)
    fwd = counts.qflash_call(op, gqa, JOB)
    q_elems = 72 * 1024 * 64
    assert fwd.flops == 4 * (72 * 1024 * 1024 / 2) * 64
    assert fwd.bytes == q_elems + 2 * q_elems / 4 + 4 * q_elems + 72 * 1024 * 4


def test_ideal_time_takes_the_larger_bound():
    p = peaks.peaks_for("TPU v5 lite")
    fast = counts.Call("c", p["bf16_flops"], 1.0, 2)     # 1 s of compute
    slow = counts.Call("m", 1.0, p["hbm_bytes_per_s"], 1)  # 1 s of bytes
    got = counts.ideal_seconds([fast, slow], p)
    assert got == {"seconds": pytest.approx(3.0),
                   "compute_bound_s": pytest.approx(2.0),
                   "memory_bound_s": pytest.approx(1.0)}


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_against_xla_cost_analysis_of_an_f32_step():
    """A plain f32 step (no recompute) at a toy size where the dense
    matmuls are nearly all the work: XLA's count of its operations is
    the model count, give or take the elementwise work and the attention
    (XLA does the whole S x S, the count its causal half)."""
    import dense_decoder as ref
    s = dict(ref.TINY, d_model=256, d_ff=1024, head_dim=64, vocab=2048,
             tie_embeddings=False, remat=False)
    job = {"batch": 4, "seq": 16}
    params = ref.init_params(s, jax.random.PRNGKey(0))
    tok = jnp.zeros((job["batch"], job["seq"]), jnp.int32)

    def loss(p):
        x = p["embed"][tok]
        for i in range(s["n_layers"]):
            layer = jax.tree_util.tree_map(lambda a: a[i], p["segments"][0])
            x = ref._layer(s, ref._plain, x, layer)
        logits = ref._rms(x, p["final_norm"]["scale"]) @ p["head"]
        return jnp.mean(jax.nn.logsumexp(logits, -1))

    cost = jax.jit(jax.grad(loss)).lower(params).cost_analysis()
    xla = cost["flops"]
    tokens = job["batch"] * job["seq"]
    model = counts.model_flops_per_token(s, job) * tokens
    assert model == pytest.approx(xla, rel=0.05)
