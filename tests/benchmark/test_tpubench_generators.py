"""The traffic generators are pure functions of the seed."""

import json

import pytest

from tpubench import spec
from tpubench.generators import lognormal

ENGINE = {"n_slots": 32, "block_size": 16, "prefill_chunk": None}
DOC_ENGINE = {"n_slots": 6, "block_size": 16, "prefill_chunk": 512}
VOCAB = 32000


def _traffic(name):
    with open(f"{spec.HERE}/traffic/{name}.json") as f:
        return json.load(f)


def _gen(name, seed, **kw):
    t = _traffic(name)
    mod = spec.generator(t["generator"])
    args = dict(window_s=40.0, warm_s=t["warm_s"], rate_rps=4.0,
                engine=DOC_ENGINE if name == "docqa" else ENGINE)
    args.update(kw)
    return mod.generate(t["params"], seed, VOCAB, **args)


@pytest.mark.parametrize("mix", ["chat", "chat-batch", "docqa"])
def test_same_seed_same_schedule_other_seed_another(mix):
    a, b, c = _gen(mix, 7), _gen(mix, 7), _gen(mix, 8)
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    assert a["pool"] != c["pool"]
    assert [r["max_tokens"] for r in a["main"]] != \
        [r["max_tokens"] for r in c["main"]]


@pytest.mark.parametrize("mix", ["chat", "chat-batch"])
def test_lognormal_lengths_hold_their_clips_and_medians(mix):
    s = _gen(mix, 3)
    p = _traffic(mix)["params"]
    plens = [len(s["pool"][r["parts"][0]]) for r in s["main"]]
    olens = [r["max_tokens"] for r in s["main"]]
    assert min(plens) >= p["prompt"]["min"] and max(plens) <= p["prompt"]["max"]
    assert min(olens) >= p["output"]["min"] and max(olens) <= p["output"]["max"]
    med = sorted(plens)[len(plens) // 2]
    assert abs(med - p["prompt"]["median"]) <= 0.1 * p["prompt"]["median"]
    # every token id is a valid one, every prompt its own
    assert all(0 <= t < VOCAB for r in s["main"] for t in s["pool"][r["id"]])
    firsts = {tuple(s["pool"][r["id"]][:16]) for r in s["main"]}
    assert len(firsts) == len(s["main"])


def test_stratified_lengths_carry_the_same_work_under_every_seed():
    tot = []
    for seed in (1, 2, 3):
        s = _gen("chat", seed)
        tot.append(sum(len(s["pool"][r["id"]]) + r["max_tokens"]
                       for r in s["main"]))
    assert max(tot) - min(tot) <= 0.02 * min(tot)


def test_open_loop_arrivals_fixed_count_inside_their_phase():
    s = _gen("chat", 5, rate_rps=4.0)
    assert s["loop"] == "open" and s["clients"] is None
    assert len(s["main"]) == 160 and len(s["warm"]) == 32
    assert all(0.0 <= r["due"] < 40.0 for r in s["main"])
    assert all(-8.0 <= r["due"] < 0.0 for r in s["warm"])
    assert [r["due"] for r in s["main"]] == sorted(r["due"] for r in s["main"])


def test_open_loop_without_a_rate_is_an_error():
    with pytest.raises(ValueError):
        _gen("chat", 1, rate_rps=None)


def test_closed_loop_takes_its_clients_from_the_engine():
    s = _gen("chat-batch", 1, rate_rps=None)
    assert s["loop"] == "closed" and s["clients"] == ENGINE["n_slots"]
    assert all("due" not in r for r in s["main"])


def test_prompt_lengths_lie_on_the_grid_and_shapes_cover_all_of_it():
    p = _traffic("chat")["params"]["prompt"]
    grid = lognormal.grid_lengths(p)
    assert grid[0] == 64 and grid[-1] == 1472 and len(grid) == 23
    for seed in (1, 2):
        s = _gen("chat", seed)
        assert {len(s["pool"][r["id"]]) for r in s["main"] + s["warm"]} <= set(grid)
        # the same shape requests whatever the seed drew, in length
        assert [len(s["pool"][r["id"]]) for r in s["shapes"]] == grid
    assert lognormal.snap(70.0, p) == 64 and lognormal.snap(9999.0, p) == 1472
    assert lognormal.snap(97.0, p) == 128 and lognormal.snap(95.0, p) == 64


def test_docqa_documents_asks_and_interleaving():
    s = _gen("docqa", 11)
    p = _traffic("docqa")["params"]
    assert s["loop"] == "closed" and s["clients"] == p["clients"]
    asks = s["main"]
    assert len(asks) == p["n_docs_main"] * p["asks_per_doc"]
    by_doc = {}
    for i, r in enumerate(asks):
        doc, q = r["parts"]
        assert len(s["pool"][doc]) in p["doc_tokens"]
        assert len(s["pool"][q]) in p["question_tokens"]
        assert p["answer_tokens"][0] <= r["max_tokens"] <= p["answer_tokens"][1]
        by_doc.setdefault(r["doc"], []).append((i, r["ask"]))
    # every question is fresh; the asks of a document come in order,
    # spread among those of the next five documents
    assert len({r["parts"][1] for r in asks}) == len(asks)
    first = {d: v[0][0] for d, v in by_doc.items()}
    for d, v in by_doc.items():
        assert [a for _, a in v] == list(range(p["asks_per_doc"]))
        later = [first[e] for e in range(d + 1, d + 7) if e in first]
        if len(later) == 6:
            assert v[1][0] > later[0]       # not back to back with its own
            assert v[-1][0] < later[5]      # done before the sixth next one
    # lengths go round: every five documents hold each length once
    lens = [len(s["pool"][f"md{d}"]) for d in range(p["n_docs_main"])]
    for k in range(0, len(lens), len(p["doc_tokens"])):
        assert sorted(lens[k:k + 5]) == sorted(p["doc_tokens"])


def test_docqa_background_and_shapes():
    s = _gen("docqa", 2)
    p = _traffic("docqa")["params"]
    assert len(s["background"]) == p["background"]["streams"]
    assert s["background"][0]["max_tokens"] == p["background"]["max_tokens"]
    # every (document, question) pair of lengths once cold, once warm
    pairs = [(len(s["pool"][r["parts"][0]]), len(s["pool"][r["parts"][1]]))
             for r in s["shapes"]]
    want = [(d, q) for d in p["doc_tokens"] for q in p["question_tokens"]]
    assert pairs[0::2] == want and pairs[1::2] == want
    assert [r["parts"][0] for r in s["shapes"][0::2]] == \
        [r["parts"][0] for r in s["shapes"][1::2]]
    assert len({r["parts"][1] for r in s["shapes"]}) == len(s["shapes"])
    # document lengths are whole chunks, so a cold admission is mid
    # chunks and a final chunk of the question alone
    assert all(n % 512 == 0 for n in p["doc_tokens"])
