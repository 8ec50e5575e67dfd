"""Least operations and bytes against hand counts at small shapes, the
slot schedule against the program's own count of decode steps, and the
peaks table."""

import pytest

from bench.harness import peaks, work

# a tiny MLA decoder: every count below is worked out by hand from it
CFG = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
       "intermediate_size": 16, "vocab_size": 10, "q_lora_rank": 4,
       "kv_lora_rank": 4, "qk_nope_head_dim": 2, "qk_rope_head_dim": 2,
       "v_head_dim": 3, "tie_word_embeddings": False}
# per layer: wq_a 8*4=32, wq_b 4*2*(2+2)=32, wkv_a 8*(4+2)=48,
# wk_b 4*2*2=16, wv_b 4*2*3=24, wo 2*3*8=48, mlp 3*8*16=384 -> 584
LAYER = 584
NORMS = 2 * 8 + 4 + 4          # ln1, ln2, q_norm, kv_norm
HEAD = 8 * 10


def test_parameter_counts():
    assert work.layer_matmul_params(CFG) == LAYER
    assert work.layer_norm_params(CFG) == NORMS
    assert work.weight_bytes_read(CFG) == 2 * (2 * (LAYER + NORMS) + HEAD + 8)


def test_decode_step_counts():
    flops, nbytes = work.decode_step(CFG, [3, 5])
    # weights per token: 2 * (2 layers * 584 + head 80), two tokens;
    # latent attention: 2 * layers * heads * (2 * 4 + 2) per position
    assert flops == 2 * 2 * (2 * LAYER + HEAD) + 2 * 2 * 2 * (2 * 4 + 2) * 8
    # weights once + 2 embedding rows + 8 live positions of
    # (4 + 2) bf16 values in each of 2 layers
    assert nbytes == work.weight_bytes_read(CFG) + 2 * 8 * 2 + 8 * 2 * 6 * 2


def test_prefill_counts():
    flops, nbytes = work.prefill(CFG, 3)
    # 3 tokens through the layers, the head once, causal pairs 6 of
    # per-head (nope + rope) scores and v sums: 2 * 2 * 2 * (2+2+3) * 6
    assert flops == 2 * 3 * 2 * LAYER + 2 * HEAD + 2 * 2 * 2 * 7 * 6
    assert nbytes == work.weight_bytes_read(CFG) + 3 * 8 * 2 + 3 * 2 * 6 * 2


def test_compaction_bytes():
    assert work.gather_bytes(100, 90) == 190
    assert work.filter_bytes(70) == 140


def test_roofline_takes_the_larger_bound():
    peak = peaks.peaks("TPU v5 lite")
    assert work.roofline_s(197e12, 1.0, peak) == pytest.approx(1.0)
    assert work.roofline_s(1.0, 819e9 * 2, peak) == pytest.approx(2.0)


@pytest.mark.parametrize("max_new,slots,requests,steps", [
    (4, 2, 2, 3),        # every request admitted at once: max_new - 1
    (4, 1, 3, 9),        # one slot: each request decodes alone
    (1, 2, 5, 0),        # the prefill token is the whole request
    (3, 2, 3, 4),        # third request waits for a free slot
])
def test_slot_schedule_steps(max_new, slots, requests, steps):
    sched = work.slot_schedule(max_new, slots, requests)
    assert len(sched) == steps
    assert all(len(s) <= slots for s in sched)
    # every request decodes max_new - 1 tokens
    per = {}
    for s in sched:
        for r, _ in s:
            per[r] = per.get(r, 0) + 1
    assert all(per.get(r, 0) == max_new - 1 for r in range(requests))


def test_generate_call_sums():
    w = work.generate_call(CFG, [3, 5], max_new=3, n_slots=2)
    assert w["decode_steps"] == 2
    # step 1: live 3+1, 5+1; step 2: 3+2, 5+2
    assert w["decode"][0] == work.decode_step(CFG, [4, 6])
    assert w["decode"][1] == work.decode_step(CFG, [5, 7])
    assert w["model_flops"] == pytest.approx(
        sum(f for f, _ in w["prefill"]) + sum(f for f, _ in w["decode"]))


def test_unknown_device_kind_raises():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
