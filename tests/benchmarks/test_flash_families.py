"""`flash_ms` and `flash_roofline` on the three families of attention kernels
they find: jax's flash kernels (what `ops/attention.py` calls today), jax's
splash kernels, and any Pallas call under the scope `gt.attn.core`, which is
priced by the model's work and not by its calls. Labels by hand, as the
compiled step carries them (the splash ones as `fixtures/splash_mha-instructions.hlo.txt`
recorded them)."""

import os
import re

import pytest

from benchmarks import cells, flops, scopes, trace

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
R0 = "gt.layers.r0"
FWD = "jit(train_step)/jvp(%s)/while/body/closed_call/" % R0
BWD = "jit(train_step)/transpose(jvp(%s))/while/body/closed_call/checkpoint/" % R0
REMAT = BWD + "rematted_computation/"


def read(name, run):
    return cells.load_module(REPO, "benchmarks/layer_metrics/%s.py" % name).read(run)


def flash_ms():
    return cells.load_module(REPO, "benchmarks/layer_metrics/flash_ms.py")


def label(instruction, op_name):
    return trace._label("%%%s = f32[8] custom-call(...)" % instruction, {instruction: op_name})


def run_of(ops, cell="qwen7-c1-s2k"):
    return {"trace": {"ops_a_step": ops}, "peak": PEAK, "cell": cells.load_cell(REPO, cell)}


def least_by_call(calls, shape):
    return sum(n * flops.flash_kernel_cost(kind, *shape)["flops"] / PEAK["bf16_flops_per_s"]
               for kind, n in calls.items())


def flash_family():
    inner = "jit(flash_attention)/"
    return {
        label("flash_attention.11", FWD + inner + "pallas_call"): [2e-3, 2.0],
        label("flash_attention.16", REMAT + inner + "pallas_call"): [2e-3, 2.0],
        label("flash_mha_bwd_dkv_block_q_major_1024_block_k_512.12", BWD + inner + "pallas_call"): [4e-3, 2.0],
        label("flash_mha_bwd_dq_block_q_major_1024_block_k_512.13", BWD + inner + "pallas_call"): [3e-3, 2.0],
        label("fusion.1", FWD + "gt.mlp/dot_general"): [50e-3, 2.0],
    }


def splash_family(scope=""):
    inner = scope + "jit(_splash_attention)/%s/%s/pallas_call"
    names = {"fwd": "splash_mha_fwd_residuals", "dkv": "splash_mqa_dkv_no_residuals",
             "dq": "splash_mha_dq_segmented_no_residuals"}
    return {
        label(names["fwd"] + ".1", FWD + inner % (names["fwd"], names["fwd"])): [2e-3, 2.0],
        label(names["fwd"] + ".2", REMAT + inner % (names["fwd"], names["fwd"])): [2e-3, 2.0],
        label(names["dkv"] + ".1", BWD + inner % (names["dkv"], names["dkv"])): [4e-3, 2.0],
        label(names["dq"] + ".1", BWD + inner % (names["dq"], names["dq"])): [3e-3, 2.0],
        label("fusion.1", FWD + "gt.mlp/dot_general"): [50e-3, 2.0],
    }


@pytest.mark.parametrize("family", [flash_family, splash_family], ids=["flash", "splash"])
def test_jaxs_two_families_are_priced_by_kind_and_count(family):
    """4 forward calls (2 recomputed), 2 dkv, 2 dq at the cell's call shape:
    the same reading whichever of jax's kernels runs."""
    run = run_of(family())
    assert read("flash_ms", run) == pytest.approx(11.0)
    found = flash_ms().per_kernel(run)
    assert {k: c for k, (_, c) in found.items()} == {"fwd": 4.0, "dkv": 2.0, "dq": 2.0}
    assert not any(c for _, c in flash_ms().core(run).values())
    least = least_by_call({"fwd": 4, "dkv": 2, "dq": 2}, (4, 28, 2048, 128))
    assert read("flash_roofline", run) == pytest.approx(100 * least / 11e-3, rel=1e-12)


def test_the_splash_names_are_jaxs_own():
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as sk

    kernels = flash_ms().KERNELS
    for phase, kind in (("fwd", "fwd"), ("dkv", "dkv"), ("dq", "dq")):
        for mqa in (False, True):
            for segmented in (False, True):
                for residuals in ((False, True) if phase == "fwd" else (False,)):
                    name = sk.get_kernel_name(mqa, residuals, segmented, phase) + ".3"
                    hits = [k for k, rx in kernels.items() if re.search(rx, name + ":jvp__/pallas_call")]
                    assert hits == [kind], name


def core_family(remat=True, bwd=True):
    """A kernel of the repo's own under `gt.attn.core`, cut into calls as it
    likes: a forward of two calls, a backward of one."""
    core = "gt.attn.core/"
    ops = {
        label("my_attn_fwd_a.1", FWD + core + "pallas_call"): [1.5e-3, 2.0],
        label("my_attn_fwd_b.2", FWD + core + "pallas_call"): [0.5e-3, 2.0],
        # what wraps the calls inside the scope is not a kernel, nor is XLA's copy of
        # a kernel's output, which inherits the call's op_name (fixtures/splash_mha-instructions.hlo.txt)
        label("fusion.9", FWD + core + "transpose"): [0.25e-3, 2.0],
        label("copy.3", FWD + core + "pallas_call"): [0.125e-3, 2.0],
        label("copy-done.4", FWD + core + "pallas_call"): [0.125e-3, 2.0],
        label("fusion.1", FWD + "gt.mlp/dot_general"): [50e-3, 2.0],
    }
    if remat:
        ops[label("my_attn_fwd_a.3", REMAT + core + "pallas_call")] = [1.5e-3, 2.0]
        ops[label("my_attn_fwd_b.4", REMAT + core + "pallas_call")] = [0.5e-3, 2.0]
    if bwd:
        ops[label("my_attn_bwd.5", BWD + core + "pallas_call")] = [5e-3, 2.0]
    return ops


@pytest.mark.parametrize("remat,bwd,ms,products", [
    (True, True, 9.0, 2 + 2 + 5), (False, True, 7.0, 2 + 5), (False, False, 2.0, 2)],
    ids=["forward_recomputed_backward", "no_recomputation", "forward_alone"])
def test_a_kernel_under_gt_attn_core_is_priced_by_the_models_work(remat, bwd, ms, products):
    """`qwen7-c1-s2k`: 2 attention layers x 4 rows x 28 heads x 2048^2 x 128,
    the causal half once: 2 products a forward the trace shows, 5 for the
    backward, however many calls make them."""
    run = run_of(core_family(remat, bwd))
    assert read("flash_ms", run) == pytest.approx(ms)
    assert not any(c for _, c in flash_ms().per_kernel(run).values())
    one_product = 2.0 * 4 * 28 * 2048 * 2048 * 128 * 0.5
    least = 2 * products * one_product / PEAK["bf16_flops_per_s"]
    assert read("flash_roofline", run) == pytest.approx(100 * least / (ms * 1e-3), rel=1e-12)
    assert flops.flash_kernel_cost("core_bwd", 4, 28, 2048, 128)["flops"] == 5 * one_product
    # jax's two backward kernels make the scores and dP twice, 7 products: on
    # the model's 5 a kernel that does what they do reads 5 / 7 of their share
    assert (flops.FLASH_KERNEL_MATMULS["dkv"] + flops.FLASH_KERNEL_MATMULS["dq"],
            flops.FLASH_KERNEL_MATMULS["core_bwd"]) == (7, 5)


def test_jaxs_kernels_called_under_the_scope_are_the_scopes_and_counted_once():
    run = run_of(splash_family(scope="gt.attn.core/"))
    assert not any(c for _, c in flash_ms().per_kernel(run).values())
    assert {k: c for k, (_, c) in flash_ms().core(run).items()} == {"fwd": 2.0, "remat": 2.0, "bwd": 4.0}
    assert read("flash_ms", run) == pytest.approx(11.0)
    # and the layer parts book them once: under the nested scope, not as "flash"
    from benchmarks.layer_metrics import layers_rest_ms

    parts = layers_rest_ms.parts(run)
    assert parts["flash"] == 0.0 and parts["gt.attn.core"] == pytest.approx(11.0)
    assert sum(parts.values()) == pytest.approx(scopes.ms_a_step(run, scopes.LAYERS))


def test_the_models_layers_are_the_attention_layers_a_device_runs():
    roofline = cells.load_module(REPO, "benchmarks/layer_metrics/flash_roofline.py")
    layers = {w: roofline.softmax_layers(cells.load_cell(REPO, w)) for w in (
        "qwen7-c1-s2k", "qwen7-c4-tp2dp2", "qwen7-c4-pp2tp2", "granite4h-c1-s4k", "kimilin-c1-s8k",
        "lfm2moe-c1-s8k", "laguna-c1-s8k")}
    # depth; a stage's half of it; the `attention` / `full_attention` entries of the first `num_layers`
    assert layers == {"qwen7-c1-s2k": 2, "qwen7-c4-tp2dp2": 4, "qwen7-c4-pp2tp2": 2, "granite4h-c1-s4k": 1,
                      "kimilin-c1-s8k": 1, "lfm2moe-c1-s8k": 1, "laguna-c1-s8k": 2}


def test_under_a_pipeline_the_models_work_is_a_stages_layers_on_all_the_rows():
    """pp2 x tp2, 4 microbatches of 2 rows: 2 layers x 8 rows x 14 heads a
    device, whatever the ticks' calls (the padding tick's are in the time)."""
    ops = {label("my_attn_fwd.1", "jit(plain_step)/jvp()/while/body/closed_call/vmap(gt.attn.core)/pallas_call"): [4e-3, 10.0],
           label("my_attn_bwd.2", "jit(plain_step)/transpose(jvp())/while/body/closed_call/vmap(vmap())/checkpoint/gt.attn.core/pallas_call"): [10e-3, 10.0],
           label("fusion.3", "jit(plain_step)/jvp(gt.head_loss)/dot_general"): [9e-3, 1.0]}
    run = run_of(ops, cell="qwen7-c4-pp2tp2")
    assert read("flash_ms", run) == pytest.approx(14.0)
    one_product = 2.0 * 8 * 14 * 2048 * 2048 * 128 * 0.5
    least = 2 * (2 + 5) * one_product / PEAK["bf16_flops_per_s"]
    assert read("flash_roofline", run) == pytest.approx(100 * least / 14e-3, rel=1e-12)


def test_no_kernel_of_any_family_leaves_both_metrics_out():
    run = run_of({label("fusion.1", FWD + "gt.mlp/dot_general"): [50e-3, 2.0],
                  label("window_attn_fwd.2", FWD + "gt.attn.band/pallas_call"): [3e-3, 3.0]})
    assert read("flash_ms", run) is None and read("flash_roofline", run) is None
