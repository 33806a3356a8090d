"""The latent-attention expert cell (``serve_latent``) at smoke widths on
the CPU, past the look for a chip: a sound run reads correct; a decode
that leaves out the rotary key, or one held expert, does not; its three
per-layer readers return numbers; and ``counts_latent`` against hand
counts at DeepSeek-V2-Lite's widths."""
import dataclasses
import importlib.util
import json
import types

import pytest

from chip_cells import CHIP, _write, execute
from benchmarks.chip import counts_latent as C
from benchmarks.chip import runtime, spec
from benchmarks.chip.drivers import serve_latent

CONF = json.loads((CHIP / "configs" / "dsv2lite-serve-ep8.json").read_text())
MODEL = CONF["model"]
#: smoke widths (the registry's, with a vocabulary no axis pads), 4 of 8
#: routed experts held; each with the published key that names it
SMOKE = {"n_layers": ("num_hidden_layers", 3), "d_model": ("hidden_size", 64),
         "n_heads": ("num_attention_heads", 4),
         "n_kv_heads": ("num_key_value_heads", 4),
         "d_ff": ("intermediate_size", 256), "vocab": ("vocab_size", 128),
         "kv_lora_rank": ("kv_lora_rank", 32),
         "qk_nope_head_dim": ("qk_nope_head_dim", 16),
         "qk_rope_head_dim": ("qk_rope_head_dim", 8),
         "v_head_dim": ("v_head_dim", 16),
         "n_experts": ("n_routed_experts", 8),
         "experts_held": ("experts_held", 4),
         "top_k": ("num_experts_per_tok", 2),
         "n_shared_experts": ("n_shared_experts", 1),
         "moe_d_ff": ("moe_intermediate_size", 32)}
WORKLOAD = "smoke.longchat"
READERS = ["latent_decode_hbm_roofline", "latent_prefill_roofline",
           "moe_rows_per_routed"]


def latent_cell(tmp, rate: float = 20.0):
    conf = json.loads(json.dumps(CONF))
    for key, (published, v) in SMOKE.items():
        conf["model"][key] = v
        conf[published] = v
    # weights drawn at 0.02 * sqrt(2048 / 64): activations of the size the
    # published widths give, so attention and each expert move the logits
    conf["model"]["init_std"] = 0.1
    conf["reduced"] = sorted(SMOKE) + ["init_std"]
    conf["deployment"].update(slots=4, s_max=64)
    # every finished request is checked; at these widths a sound run on
    # the CPU reads a mean gap of 3.3e-5 (bfloat16 program against the
    # float32 reference), a decode without the rotary key 0.115 and one
    # held expert left out 0.0016
    conf["check"].update(sample_tokens=1000, logit_gap_mean_limit=4e-4)
    mix = {"generator": "open_loop",
           "arrival": {"kind": "poisson", "rate_per_s": rate},
           "prompt_len": {"kind": "choice", "values": [8, 16, 24],
                          "weights": [0.4, 0.4, 0.2]},
           "output_len": {"kind": "lognormal", "median": 6, "sigma": 0.6,
                          "min": 2, "max": 12}}
    _write(tmp, "smoke-latent", conf, WORKLOAD, "smoke_longchat", mix, 1,
           ["serve_tokens_per_s"], READERS + ["decode_step_ms"])
    return tmp


def test_latent_cell_runs_and_reads_correct(tmp_path):
    root = latent_cell(tmp_path)
    out = execute(root, WORKLOAD, 2**31 + 11, 1.5)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 30
    assert out["checks"]["moe_dropped_rows"] == {"value": 0, "limit": 0}
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    traced = execute(root, WORKLOAD, 2**31 + 11, 1.5, trace=True)
    # no TPU plane on the CPU: the device readers find nothing; the
    # expert layer's count is there, and dropless
    assert traced["metrics"]["moe_rows_per_routed"]["value"] == 1.0
    assert "decode_step_ms" not in traced["metrics"]


def _broken(monkeypatch, fault):
    import repro.models.attention as ATT
    import repro.models.moe as MOE
    if fault == "no_rotary_key":
        real = ATT.attn_latent_decode

        def attn(q_lat, q_pe, c, kr, *, kv_len, new, scale):
            c_new, kr_new, slot = new
            return real(q_lat, q_pe, c, 0 * kr, kv_len=kv_len,
                        new=(c_new, 0 * kr_new, slot), scale=scale)
        monkeypatch.setattr(ATT, "attn_latent_decode", attn)
    else:
        real = MOE.dropless_experts

        def experts(ops, p, *a, **k):
            return real(ops, dict(p, wd=p["wd"].at[0].set(0)), *a, **k)
        monkeypatch.setattr(MOE, "dropless_experts", experts)


@pytest.mark.parametrize("fault", ["no_rotary_key", "no_expert_0"])
def test_latent_faults_read_not_correct(tmp_path, monkeypatch, fault):
    _broken(monkeypatch, fault)
    out = execute(latent_cell(tmp_path, rate=60.0), WORKLOAD, 2**31 + 21,
                  1.0)
    assert out["correct"] is False
    gap = out["checks"]["logit_gap_mean"]
    assert gap["value"] > gap["limit"]


def _reader(name):
    path = CHIP / "metrics" / f"{name}.py"
    s = importlib.util.spec_from_file_location("r_" + name, path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.read


def test_readers_on_a_run_record(tmp_path):
    """The three readers on the record a CPU run leaves, given a trace
    summary with the two programs' device times: each by hand."""
    cell = spec.load_cell(WORKLOAD, latent_cell(tmp_path))
    ctx = types.SimpleNamespace(
        compiles=runtime.CompileCounter(), log=lambda m: None,
        tracer=runtime.Tracer(False, ""))
    rec = serve_latent.run(cell, 2**31 + 5, 1.0, ctx)["record"]
    steps = rec["decode_steps"]
    rec = dict(rec, peaks=spec.peaks_for("TPU v5 lite"),
               trace={"window_ns": 1e9, "devices": {0: {"modules": {
                   "jit_decode(7)": (steps, 2e6 * steps),
                   "jit_prefill(3)": (rec["prefills"], 9e6)}}}})
    m, c = rec["model"], rec["counters"]
    assert c["decode.moe.routed_rows"] == c["decode.moe.expert_rows"] > 0

    got = _reader("latent_decode_hbm_roofline")(rec)
    need = C.decode_bytes(m, steps, c["decode.moe.experts_touched"],
                          rec["live"], rec["active"])
    assert got == pytest.approx(100 * need / (819e9 * 2e-3 * steps))
    got = _reader("latent_prefill_roofline")(rec)
    flops = C.prefill_flops(m, rec["prompt_tokens"], rec["prefills"],
                            c["prefill.moe.routed_rows"], rec["prompt_pairs"])
    assert got == pytest.approx(100 * flops / (197e12 * 9e-3))
    assert _reader("moe_rows_per_routed")(rec) == 1.0
    for name in READERS:
        assert _reader(name)({}) is None


def test_counts_by_hand():
    m = MODEL
    assert m["experts_held"] == 8 and m["n_experts"] == 64
    # attention: W_q 2048 x 16*192, W_dkv 2048 x 576, kv_norm 512,
    # W_ukv 512 x 16*256, W_o 16*128 x 2048, ln1 2048
    attn = 2048 * 3072 + 2048 * 576 + 512 + 512 * 4096 + 2048 * 2048 + 2048
    assert C.attn_params(m) == attn == 13_765_120
    expert = 3 * 2048 * 1408
    assert C.expert_params(m) == expert == 8_650_752
    dense = attn + 2048 + 3 * 2048 * 10944
    moe = attn + 2048 + 2048 * 64 + 8 * expert + 2 * expert
    assert C.layer_params(m, False) == dense
    assert C.layer_params(m, True) == moe
    held = dense + 26 * moe + 2 * 102400 * 2048 + 2048
    assert C.held_params(m) == held == 3_110_989_312      # 6.22 GB in bf16
    non_expert = held - 102400 * 2048 - 26 * 8 * expert
    assert C.non_expert_params(m) == non_expert
    # the latent cache: (512 + 64) values x 2 bytes x 27 layers
    assert C.latent_bytes_per_position(m) == 576 * 2 * 27 == 31_104
    assert C.decode_bytes(m, 3, 100, 5000, 16) == \
        3 * 2 * non_expert + 100 * 2 * expert + 31_104 * 5016
    # one 6144-token prompt routing 6144 * 0.75 rows to the held experts
    pairs = 6144 * 6145 // 2
    assert C.causal_pairs(6144) == pairs
    head = 2048 * 102400
    want = 2 * ((non_expert - head) * 6144 + head + 4608 * expert
                + 27 * 16 * (192 + 128) * pairs)
    assert C.prefill_flops(m, 6144, 1, 4608, pairs) == want
    # the quadratic part is over a quarter of that prefill
    assert 2 * 27 * 16 * 320 * pairs > want / 4


def test_program_config_checks_the_latent_and_expert_sizes():
    from repro.configs import get_config
    cfg = serve_latent.program_config(CONF)
    assert cfg.experts_held == 8 and cfg.n_experts == 64 and cfg.mla
    assert not cfg.norm_topk_prob
    assert cfg == dataclasses.replace(get_config("deepseek-v2-lite"),
                                      experts_held=8)
    for key, published in (("kv_lora_rank", "kv_lora_rank"),
                           ("top_k", "num_experts_per_tok")):
        bad = json.loads(json.dumps(CONF))
        bad["model"][key] += 1
        with pytest.raises(ValueError, match=published):
            serve_latent.program_config(bad)
        bad[published] += 1             # the file agrees with itself ...
        with pytest.raises(ValueError, match=key):   # ... not the program
            serve_latent.program_config(bad)
