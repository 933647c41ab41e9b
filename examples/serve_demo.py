"""Serving demo: prefill + batched greedy decode with KV caches on a small
dense LM (the serve-side public API).

    PYTHONPATH=src python examples/serve_demo.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_smoke_config
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.models.layers import MeshCtx


def main():
    cfg = get_smoke_config("stablelm_12b").with_(dtype="float32")
    mesh = make_host_mesh()
    mcx = MeshCtx(mesh=mesh, dp=("data",), tp="model")
    mdl = M.build(cfg, mcx)
    params = mdl.init_params(jax.random.PRNGKey(0))

    B, S, gen = 4, 24, 16
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                 cfg.vocab_size)
    prefill = jax.jit(mdl.prefill_step)
    decode = jax.jit(mdl.decode_step)

    tok, caches = prefill(params, {"tokens": prompts})
    out = [np.asarray(tok)]
    for t in range(gen - 1):
        tok, caches = decode(params, caches, tok,
                             jnp.asarray(S + t, jnp.int32))
        out.append(np.asarray(tok))
    gen_tokens = np.stack(out, axis=1)
    print(f"[serve] prompts {prompts.shape} -> generated {gen_tokens.shape}")
    for b in range(B):
        print(f"  seq{b}: {gen_tokens[b][:12]} ...")
    print("[serve] ok")


if __name__ == "__main__":
    main()
