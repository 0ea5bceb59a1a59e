"""A cell small enough for the CPU: the benchmark's tests drive the
harness on it with the chip look skipped.

Its limits were set like a cell's, from readings at this size on the CPU
(``bench/calibrate.py``'s ``readings_over_seeds``, 8 seeds for the
program, 3 for the control and the half-batch fault):

=========== =============== =============== ================= =====
number      program, max    control, min    half batch, min   limit
=========== =============== =============== ================= =====
loss_gap    1.19e-2         2.54e-2         7.41e-2           0.025
grad_gap    1.09e-2         5.34e-2         4.12e-2           0.025
update_gap  3.10e-3         4.31e-3         4.22e-2           0.01
=========== =============== =============== ================= =====
"""
import _bench

cells = _bench.module("cells")

LIMITS = {"loss_gap": 0.025, "grad_gap": 0.025, "update_gap": 0.01}


def cell(recipe="paper_fp4", linear_impl="qdq", dtype="bfloat16",
         batch=8, limits=LIMITS):
    conf = {"arch": "gpt2-774m", "model": dict(
        n_layers=2, d_model=256, n_heads=4, n_kv_heads=4, head_dim=64,
        d_ff=1024, vocab_size=512, activation="gelu", norm="layernorm",
        pos_emb="learned", max_seq_len=128, tie_embeddings=True,
        dtype=dtype, attention_impl="chunked", linear_impl=linear_impl)}
    work = dict(config="tiny", recipe=recipe, global_batch=batch,
                seq_len=128, mesh_shape=None, fsdp=True, total_steps=100000,
                warmup_frac=0.0015, learning_rate=6e-4,
                data={"n_patterns": 512, "noise": 0.02}, trace_steps=2,
                limits=dict(limits))
    return cells.Cell("tiny", 1, conf, work)
