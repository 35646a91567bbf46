"""The traffic generator: lengths, clipping and seeding."""
import numpy as np

import traffic

CHAT = {"kind": "closed_loop", "clients": 4, "requests_per_client": 10,
        "prompt": {"dist": "lognormal", "median": 384, "sigma": 0.8,
                   "min": 64, "max": 2048},
        "output": {"dist": "lognormal", "median": 320, "sigma": 0.8,
                   "min": 32, "max": 1536}}


def test_quantile_lengths_median_and_clip():
    x = traffic.quantile_lengths(CHAT["prompt"], 1001)
    assert x.min() >= 64 and x.max() <= 2048
    assert np.median(x) == 384
    assert (x == 64).any() or x.min() > 64
    tight = dict(CHAT["prompt"], max=400)
    assert traffic.quantile_lengths(tight, 101).max() == 400
    assert (traffic.quantile_lengths({"dist": "fixed", "value": 7}, 3)
            == 7).all()


def _lengths(mix):
    return sorted((len(p), o) for c in mix["clients"] for p, o in c[1:])


def test_same_sizes_for_every_seed_in_another_order():
    a = traffic.closed_loop(CHAT, 1, 1000)
    b = traffic.closed_loop(CHAT, 2 ** 33 + 1, 1000)
    assert sorted(len(p) for c in a["clients"] for p, _ in c) == \
        sorted(len(p) for c in b["clients"] for p, _ in c)
    assert [len(p) for p, _ in a["clients"][0]] != \
        [len(p) for p, _ in b["clients"][0]]


def test_seeding_is_deterministic_and_ids_in_range():
    a = traffic.closed_loop(CHAT, 5, 1000)
    b = traffic.closed_loop(CHAT, 5, 1000)
    assert a == b
    ids = np.concatenate([p for c in a["clients"] for p, _ in c])
    assert ids.min() >= 1 and ids.max() < 1000


def test_first_requests_are_staggered():
    a = traffic.closed_loop(CHAT, 3, 1000)
    full = traffic.closed_loop(dict(CHAT, clients=1,
                                    requests_per_client=40), 3, 1000)
    assert len(full["clients"][0]) == 40
    for i, c in enumerate(a["clients"]):
        assert c[0][1] >= 32
    assert a["clients"][0][0][1] <= a["clients"][0][1][1] * 10


def test_documents_prefix_every_prompt():
    rag = dict(CHAT, documents={"count": 3, "length": 16, "zipf_s": 1.0},
               prompt={"dist": "fixed", "value": 5})
    a = traffic.closed_loop(rag, 9, 1000)
    assert len(a["documents"]) == 3
    for c in a["clients"]:
        for p, _ in c:
            assert len(p) == 21 and p[:16] in a["documents"]
    w = traffic.zipf_weights(3, 1.0)
    assert np.allclose(w, np.array([1, 1 / 2, 1 / 3]) / (11 / 6))
