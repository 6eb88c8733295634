"""Tier-1's way to the fast tests of the `minicpm_sala_9b` configuration and
its cell (benchmark/tests/test_minicpmsala_cell.py), in the manner of
tests/test_benchmark_kexaone.py: `pytest tests/` does not collect
benchmark/tests/.  The cell's whole rehearsal (`slow`, and in need of
benchmark/conftest.py's four virtual devices) stays where it is."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.tests.test_minicpmsala_cell import *   # noqa: E402,F401,F403

del test_minicpmsala_cell_rehearsal                    # noqa: F821


_the_cell_as_pr48_left_it = test_the_cell_is_the_issues          # noqa: F821


def test_the_cell_is_the_issues(manifest):                     # noqa: F811
    """benchmark/tests/ holds PR 48's cell, configuration and eight readers
    to be the manifest's LAST entries and its cell's name the last of every
    list it joined, and only a `benchmark` PR may edit that file: behind
    them stand the one reader PR 49 appended, the one PR 50 did, and PR
    51's cell, configuration, three readers and its cell's name in the
    lists, the one reader PR 53 appended, the three PR 54 did, the one
    PR 55 did, PR 56's cell, configuration and two readers, and the one
    reader PR 57 appended; the rest is as it was."""
    later = ("mimov2flash_reasoning_decode", "granite4hs_decode_saturated")
    assert [m["name"] for m in manifest["per_layer"][-13:-4]] == [
        "sparse_tiles_per_grid_step", "sparse_prefill_kernel_ms_per_prefill",
        "kinds_attention_roofline", "attention_share_of_trip",
        "full_kv_bytes_per_slot", "prefill_ahead_share",
        "tokens_sent_per_s", "client_read_share", "token_delivery_ms_mean"]
    assert [w["name"] for w in manifest["workloads"][-2:]] == list(later)
    assert [c["name"] for c in manifest["configs"][-2:]] == [
        "mimo_v2_flash", "granite_4_0_h_small"]

    def as_it_was(entries):
        return [dict(m, workloads=[w for w in m["workloads"]
                                   if w not in later])
                if "workloads" in m else m for m in entries]
    _the_cell_as_pr48_left_it(dict(
        manifest, workloads=manifest["workloads"][:-2],
        configs=manifest["configs"][:-2],
        end_to_end=as_it_was(manifest["end_to_end"]),
        per_layer=as_it_was(manifest["per_layer"][:-13])))


# the instruction of stage 2's Mosaic call as a prefill executable's text
# holds it (the 24,576 bucket compiled for a described v5e, PR 50; the
# backend_config's payload cut), and a consumer that names it as an operand
_PREFILL_TEXT = '''
  %fusion.321 = bf16[2,16,2048,128]{3,2,1,0:T(8,128)(2,1)} fusion(%convert_element_type.433), kind=kLoop, calls=%fused_computation.536, metadata={op_name="jit(_prefill_math)/while/body/closed_call/cond/branch_1_fun/gqa_attention/sparse_attention/transpose" stack_frame_id=19}
  %sparse_prefill_attention.4 = f32[2048,4096]{1,0:T(8,128)} custom-call(%copy-done.60, %fusion.321, %copy_bitcast_fusion.3, %copy_bitcast_fusion.2, %reshape.1746), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[2]{0}, bf16[2,16,2048,128]{3,2,1,0}, f32[24576,256]{1,0}, f32[24576,256]{1,0}, f32[2,384,2048]{2,1,0}}, frontend_attributes={kernel_metadata={"kernel":"sparse_prefill_attention"}}, metadata={op_name="jit(_prefill_math)/while/body/closed_call/cond/branch_1_fun/gqa_attention/sparse_attention/sparse_prefill_attention/pallas_call" stack_frame_id=19}, backend_config={"flag_configs":[]}
  %fusion.88 = f32[1,2048,4096]{2,1,0:T(8,128)} fusion(%sparse_prefill_attention.4, %p.3), kind=kLoop, calls=%fused_computation.90, metadata={op_name="jit(_prefill_math)/while/body/closed_call/cond/branch_1_fun/gqa_attention/mul" stack_frame_id=21}
  %fusion.12 = f32[512,2,384]{2,1,0:T(2,128)} fusion(%p.4), kind=kLoop, calls=%fused_computation.14, metadata={op_name="jit(_prefill_math)/while/body/closed_call/cond/branch_1_fun/gqa_attention/while/body/sparse_select/top_k" stack_frame_id=17}
'''


def test_the_prefills_scope_names_the_flash_body_of_stage_2(config):
    """`sparse_prefill_ms_per_prefill` times the instructions a bucket's
    prefill executable holds under `sparse_select` and `sparse_attention`
    (`serve_decode_ssm.step_scope_ops`): stage 2's Mosaic call is among
    them, so the reading did not fall by losing sight of stage 2; the new
    reader finds the same instruction by its name; and the step's kernel's
    readers (`kernel_trace_match.sparse_attention`, a substring of the
    event's whole instruction) do not match it."""
    from benchmark import moe_trace, xplane
    assert "sparse_attention" in config["prefill_trace_scopes"]
    names = moe_trace.scope_instruction_names(_PREFILL_TEXT,
                                              "sparse_attention")
    assert names == {"fusion.321", "sparse_prefill_attention.4"}
    assert moe_trace.scope_instruction_names(
        _PREFILL_TEXT, "sparse_select") == {"fusion.12"}
    kernel = bench_run.load_reader(                     # noqa: F821
        "sparse_prefill_kernel_ms_per_prefill").__globals__["KERNEL"]
    lines = [ln.strip() for ln in _PREFILL_TEXT.strip().splitlines()]
    assert [xplane.short_name(ln) for ln in lines
            if kernel in xplane.short_name(ln)] == [
                "sparse_prefill_attention.4"]
    step_kernel = config["kernel_trace_match"]["sparse_attention"]
    assert not [ln for ln in lines if step_kernel in ln]
