"""End-to-end Mixtral-family serving (no TPU required).

The whole MoE serving story on a tiny randomly-initialized HF Mixtral,
hardware-free:

  1. build a tiny ``transformers`` MixtralForCausalLM and convert it
     (convert.moe_from_hf) — logits parity vs the HF forward is
     asserted, not assumed;
  2. quantize the expert weights to int8 (quant.quantize_params —
     rank-generic over the [L, E, in, out] expert stacks);
  3. speculative decoding with the int8-self draft
     (speculative_generate(model="moe")) — bit-exact greedy, the
     draft only buys speed;
  4. serve the int8 tree from ONE tpushare-serve HTTP daemon
     (model_family="moe", the paged pool): two requests share a
     system prompt of one whole block, the second reports it as its
     cached prefix (block-granular prefix cache), and both streams
     match moe.generate.

Run: python demo/e2e_moe_serve.py   (forces the CPU backend itself
with jax.config.update, which wins over JAX_PLATFORMS)
"""

from __future__ import annotations

import http.client
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def main() -> int:
    import torch
    import transformers

    torch.set_num_threads(1)
    from tpushare.models import moe, quant
    from tpushare.models.convert import moe_from_hf
    from tpushare.models.speculative import speculative_generate

    # 1. A tiny HF Mixtral, converted with asserted parity.
    hf_cfg = transformers.MixtralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, num_local_experts=4,
        num_experts_per_tok=2, max_position_embeddings=64,
        sliding_window=None, attn_implementation="eager")
    torch.manual_seed(0)
    hf = transformers.MixtralForCausalLM(hf_cfg).eval()
    params, cfg = moe_from_hf(hf, dtype=jnp.float32)
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 12))
    with torch.no_grad():
        want = hf(torch.tensor(toks)).logits.float().numpy()
    got, _ = moe.forward(params, jnp.asarray(toks), cfg)
    err = float(np.max(np.abs(np.asarray(got) - want)))
    assert err < 2e-4, err
    print(f"[1] converted Mixtral ({cfg.n_experts} experts, top-"
          f"{cfg.top_k}): HF logits parity max|err| = {err:.1e}")

    # 2. Int8 expert weights.
    qp = quant.quantize_params(params, cfg)
    fp_b = sum(x.nbytes for x in jax.tree.leaves(params))
    q_b = sum(x.nbytes for x in jax.tree.leaves(qp))
    hook = quant.dequant_hook(cfg)
    print(f"[2] int8 expert weights: {fp_b/2**20:.1f} MiB -> "
          f"{q_b/2**20:.1f} MiB ({q_b/fp_b:.0%})")

    # 3. Speculative decoding, int8-self draft, exact greedy.
    prompt = jnp.asarray(toks)
    plain = moe.generate(params, prompt, cfg, max_new_tokens=10)
    spec = speculative_generate(params, qp, prompt, cfg,
                                max_new_tokens=10, gamma=3,
                                draft_layers_hook=hook, model="moe")
    assert (np.asarray(spec) == np.asarray(plain)).all()
    print("[3] speculative decoding (int8-self draft, gamma=3): "
          "bit-exact greedy vs moe.generate")

    # 4. Serve the int8 tree over HTTP.
    from tpushare.cli.serve import ServeEngine, serve
    engine = ServeEngine(qp, cfg, model_family="moe", n_slots=2,
                         n_blocks=16, block_size=16, layers_hook=hook,
                         idle_sleep_s=0.001)
    httpd = serve(engine, host="127.0.0.1", port=0, timeout_s=120.0)
    port = httpd.server_address[1]

    def post(obj):
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=120)
        conn.request("POST", "/v1/completions", json.dumps(obj),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        return r.status, json.loads(r.read())

    try:
        # One whole block: the prefix cache shares blocks, not tokens.
        system = [int(t) for t in np.random.default_rng(1).integers(
            0, cfg.vocab_size, 16)]
        s1, o1 = post({"prompt": system + [3, 1], "max_tokens": 4})
        s2, o2 = post({"prompt": system + [9, 9, 9], "max_tokens": 4})
        assert s1 == 200 and s2 == 200, (o1, o2)
        assert o2["cached_prefix"] == 16, o2
        ref = moe.generate(qp, jnp.asarray([system + [9, 9, 9]]), cfg,
                           max_new_tokens=4, layers_hook=hook)
        assert o2["tokens"] == [int(t) for t in ref[0, 19:]]
        print(f"[4] HTTP daemon (int8, prefix cache): 2nd request "
              f"reused {o2['cached_prefix']} shared prompt tokens; "
              f"streams match moe.generate")
    finally:
        httpd.shutdown()
        engine.stop()
    print("E2E MoE serve demo: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
