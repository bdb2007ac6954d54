"""The selector-and-window configuration of the latent family
(``latent.tiny()``: the dots3 cell's shape at toy widths) computes what
it computed before the family learned a second model's layers (no
selector, sandwich norms, a multi-token-prediction module): the logits
its sampler is handed over a serial prefill, decode ticks and a fused
admission, and its counters, against a record taken on the parent commit
(PR 33's tree, ``tests/data/latent_tiny_pr33.json``).

On the machine that took the record the two trees agree bit for bit
(the record's ``sha256``, compared when this file is run by hand:
``python -m tests.test_latent_unchanged --compare``); the test allows
1e-5, what another CPU's vector width may move a float32 sum by.

    python -m tests.test_latent_unchanged --record <path>   # re-record
"""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

RECORD = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "latent_tiny_pr33.json")
WHAT = ("tpushare.models.latent.tiny() seed 0 on the CPU, float32: serial "
        "prefill of 45 tokens, 3 decode ticks, a 70-token admission fused in "
        "chunks of 32 beside them, 1 more tick")


def scenario():
    """(every [slots, vocab] the sampler was handed, in order; the
    server's ``family_stats`` at the end)."""
    from tpushare.models import latent
    from tpushare.models.latent import LatentSlotServer
    cfg = latent.tiny()
    params = jax.jit(lambda k: latent.init_params(k, cfg))(
        jax.random.PRNGKey(0))
    srv = LatentSlotServer(params, cfg, n_slots=3, n_blocks=64,
                           block_size=16, max_blocks_per_slot=12,
                           prefix_cache=True)
    seen = []
    pick = srv._sampler.pick
    srv._sampler.pick = lambda lg: (
        seen.append(np.asarray(lg, np.float32)), pick(lg))[1]
    rng = np.random.default_rng(0)
    srv.admit(jnp.asarray(rng.integers(0, 256, 45), jnp.int32))
    for _ in range(3):
        srv.step()
    b = srv.admit_start(jnp.asarray(rng.integers(0, 256, 70), jnp.int32),
                        chunk_tokens=32)
    while b in srv.admission_slots:
        srv.step(prefill_work=b)
    srv.step()
    return seen, srv.family_stats()


def as_record(seen, st):
    flat = [x.reshape(-1, x.shape[-1]) for x in seen]
    return {"what": WHAT,
            "shapes": [list(x.shape) for x in seen],
            "sha256": [hashlib.sha256(
                np.ascontiguousarray(x).tobytes()).hexdigest() for x in seen],
            "logits": [x[:, :48].tolist() for x in flat],
            "argmax": [x.argmax(-1).tolist() for x in flat],
            **{k: st[k] for k in ("select_keys_kept", "select_keys_seen",
                                  "expert_assign_local", "expert_tokens")}}


@pytest.fixture(scope="module")
def pair():
    with open(RECORD) as f:
        return json.load(f), as_record(*scenario())


def test_the_selector_model_hands_its_sampler_the_parents_logits(pair):
    want, got = pair
    assert want["what"] == WHAT and got["shapes"] == want["shapes"]
    assert len(want["logits"]) == 9
    # rows of slots with no stream hold whatever an empty table gives;
    # the streams' rows: slot 0 throughout (the two [1, vocab] picks are
    # an admission's first token), slot 1 in the last tick
    live = [[0]] * 8 + [[0, 1]]
    for rows, w, g, wa, ga in zip(live, want["logits"], got["logits"],
                                  want["argmax"], got["argmax"]):
        for r in rows if len(w) > 1 else [0]:
            np.testing.assert_allclose(g[r], w[r], rtol=1e-5, atol=1e-5)
            assert ga[r] == wa[r]


def test_the_selector_models_counters_are_the_parents(pair):
    want, got = pair
    for k in ("select_keys_kept", "select_keys_seen", "expert_assign_local",
              "expert_tokens"):
        assert got[k] == want[k] and got[k] > 0, k


def main(argv):
    rec = as_record(*scenario())
    if argv[:1] == ["--record"]:
        with open(argv[1], "w") as f:
            json.dump(rec, f)
        return 0
    with open(RECORD) as f:
        want = json.load(f)
    same = rec["sha256"] == want["sha256"]
    print("bit for bit the record's" if same else "NOT bit for bit")
    return 0 if same else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
