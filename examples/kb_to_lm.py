"""End-to-end driver: materialize a KB with the TG engine, linearize the
derived facts into token sequences, and train a ~100M-parameter LM on them
for a few hundred steps (with checkpoint/restart).

    PYTHONPATH=src python examples/kb_to_lm.py [--steps 200]
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs.base import ModelConfig
from repro.data.kb_sources import LUBM_L, lubm_facts
from repro.data.pipeline import KBLinearizer
from repro.engine.materialize import EngineKB, materialize
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.models.layers import MeshCtx
from repro.train.train_loop import train


def lm_100m(vocab: int) -> ModelConfig:
    return ModelConfig(
        name="kb-lm-100m", family="dense", num_layers=8, d_model=768,
        num_heads=12, num_kv_heads=4, head_dim=64, d_ff=2304,
        vocab_size=vocab, mlp_type="swiglu", norm_type="rmsnorm",
        attn_chunk=128, loss_chunk=128, remat="none")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    # 1) materialize the KB (paper's technique)
    print("[kb] materializing LUBM-L ...")
    kb = EngineKB(LUBM_L, lubm_facts(n_univ=4))
    st = materialize(kb, mode="tg")
    print(f"[kb] derived={st.derived} triggers={st.triggers} "
          f"total={kb.num_facts()} facts")

    # 2) linearize derived facts into a token stream
    data = KBLinearizer(kb, batch=args.batch, seq=args.seq)
    print(f"[data] vocab={data.vocab_size} stream={len(data.stream)} tokens")

    # 3) train the LM
    cfg = lm_100m(data.vocab_size).with_(num_layers=args.layers)
    n = cfg.param_counts()["total"]
    print(f"[model] {n/1e6:.1f}M params")
    mesh = make_host_mesh()
    mcx = MeshCtx(mesh=mesh, dp=("data",), tp="model")
    mdl = M.build(cfg, mcx)
    ckpt = args.ckpt or os.path.join(tempfile.gettempdir(), "kb_lm_ckpt")
    params, opt, losses = train(mdl, data, steps=args.steps, ckpt_dir=ckpt,
                                ckpt_every=100, log_every=10)
    first, last = losses[0][1], losses[-1][1]
    print(f"[done] loss {first:.3f} -> {last:.3f} "
          f"({'improved' if last < first else 'NO IMPROVEMENT'})")


if __name__ == "__main__":
    main()
