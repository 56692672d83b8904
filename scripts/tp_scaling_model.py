"""TP scaling-efficiency model: measured collective traffic for the
megatron-sharded BERT step (the DP analog is scripts/scaling_model.py).

A MODEL, not a measurement (no TP step has run on real chips): this
compiles the REAL TP training step — BERT with BERT_TP_RULES param
shardings over a ``{"data": 1, "model": tp}`` mesh — at each TP width in
a fresh subprocess, executes one step, and reads the exact collective
traffic XLA inserted (all-reduce / all-gather / reduce-scatter bytes)
out of the compiled HLO. Megatron theory says TP comm per step is
activation-shaped: ~4 all-reduces of ``B*S*H`` per layer (2 fwd, 2 bwd),
invariant in tp except the ring factor (tp-1)/tp. The sweep measures
that instead of assuming it; the flagship table then projects BERT-base
SQuAD (B=32, S=384, H=768, L=12) onto v5e ICI with the measured
bytes-per-activation ratio, against compute time at stated MFU
assumptions (no real-chip BERT step exists yet to anchor on — unlike
the DP table, which uses the measured ResNet step).

Run under the virtual CPU mesh:
    JAX_PLATFORMS=cpu \
    python scripts/tp_scaling_model.py --sweep 2,4,8
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from scaling_model import ICI_BYTES_PER_SEC, collective_bytes  # noqa: E402

#: every family TP sharding can produce (the DP sweep needs only
#: all-reduce; this list is the only TP-side difference in the scan)
_TP_FAMILIES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")


def _measure(tp):
    """Compile + run the TP-sharded BERT step on a tp-device mesh."""
    import jax
    import numpy as np
    import optax

    from tensorflowonspark_tpu import training
    from tensorflowonspark_tpu.models import bert
    from tensorflowonspark_tpu.parallel import build_mesh
    from tensorflowonspark_tpu.parallel.sharding import (
        BERT_TP_RULES, tree_shardings)

    assert len(jax.devices()) == tp, (len(jax.devices()), tp)
    mesh = build_mesh({"data": 1, "model": tp})
    cfg = bert.bert_tiny()
    model = bert.BertForQuestionAnswering(cfg)
    B, S = 8, 32
    rng = np.random.RandomState(0)
    batch = {
        "input_ids": rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32),
        "attention_mask": np.ones((B, S), bool),
        "start_positions": rng.randint(0, S, (B,)).astype(np.int32),
        "end_positions": rng.randint(0, S, (B,)).astype(np.int32),
    }
    trainer = training.Trainer(
        model, optax.adamw(1e-4), mesh, loss_fn=bert.qa_span_loss,
        input_keys=("input_ids", "attention_mask"), dropout_rng=True,
        data_axis="data", constrain_state=False)
    state = trainer.init(jax.random.PRNGKey(0), batch)
    state["params"] = jax.device_put(
        state["params"], tree_shardings(state["params"], mesh,
                                        BERT_TP_RULES))
    state, metrics = trainer.step(state, batch)
    step_executed = bool(np.isfinite(float(jax.device_get(
        metrics["loss"]))))
    compiled = trainer._jit_step.lower(state, batch).compile()
    collectives = collective_bytes(compiled.as_text(),
                                   families=_TP_FAMILIES)

    # activation volume the megatron model predicts the comm tracks:
    # one [B, S, H] f32 activation
    act_bytes = B * S * cfg.hidden_size * 4
    total = sum(f["bytes"] for f in collectives.values())
    report = {
        "tp": tp,
        "step_executed": step_executed,
        "layers": cfg.num_layers,
        "activation_bytes": act_bytes,
        "collectives": collectives,
        "total_collective_bytes": total,
        # collective bytes per layer, in units of one activation: the
        # megatron fwd+bwd prediction is ~4 (ring-factor aside); the
        # sweep checks how XLA's actual strategy tracks tp
        "bytes_per_layer_per_activation": round(
            total / cfg.num_layers / act_bytes, 3),
    }
    print(json.dumps(report, indent=2))


def _sweep(tps):
    """One fresh subprocess per TP width (device count fixes at init)."""
    from scaling_model import run_width

    points = [run_width([os.path.abspath(__file__), "--tp", str(tp)],
                        tp, key="tp")
              for tp in tps]
    ok = [p for p in points if "error" not in p and p["step_executed"]]
    all_ok = len(ok) == len(points) and bool(points)

    # Flagship projection: BERT-base SQuAD shapes on v5e ICI. Use the
    # LARGEST measured ratio across widths (XLA's mix can shrink at
    # wider tp when small dims fall back to replication, so max is the
    # conservative pick and is sweep-order-independent).
    table = []
    if ok:
        ratio = max(p["bytes_per_layer_per_activation"] for p in ok)
        B, S, H, L = 32, 384, 768, 12
        comm_per_step = ratio * L * (B * S * H * 4)
        for tp in (1, 2, 4, 8):
            t_comm = comm_per_step * (tp - 1) / tp / ICI_BYTES_PER_SEC
            row = {"tp": tp, "comm_ms_per_step": round(t_comm * 1e3, 3)}
            # compute time at stated MFU assumptions — no real-chip BERT
            # step has been measured yet (unlike the DP table's anchor)
            flops = 6 * 110e6 * B * S  # ~6ND for BERT-base fwd+bwd
            for mfu in (0.3, 0.4, 0.5):
                t_compute = flops / (197e12 * mfu) / tp
                row["efficiency_at_mfu_%.1f" % mfu] = round(
                    t_compute / (t_compute + t_comm), 4)
            table.append(row)

    report = {
        "sweep": points,
        "all_points_ok": all_ok,
        "bert_base_tp_projection": table,
        "note": "collective bytes measured from the compiled TP step's "
                "HLO at each width; projection assumes zero "
                "comm/compute overlap (worst case) and the stated MFU",
    }
    print(json.dumps(report, indent=2))
    return 0 if all_ok else 1


def main():
    if "--sweep" in sys.argv:
        i = sys.argv.index("--sweep")
        arg = sys.argv[i + 1] if len(sys.argv) > i + 1 else "2,4,8"
        sys.exit(_sweep([int(s) for s in arg.split(",")]))
    tp = 2
    if "--tp" in sys.argv:
        tp = int(sys.argv[sys.argv.index("--tp") + 1])
    _measure(tp)


if __name__ == "__main__":
    main()
