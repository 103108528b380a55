"""Seeded checksums of the benchmark's generators.  They were equal to
the program's own generators when copied; these pins keep the yardstick
where it is whatever the program does later."""
import hashlib

import numpy as np

import cells  # noqa: F401  (puts bench/ on the path)
import gen


def digest(*arrays) -> str:
    m = hashlib.sha256()
    for a in arrays:
        m.update(np.ascontiguousarray(a).tobytes())
    return m.hexdigest()[:16]


def scenario():
    ds = gen.make_dataset("mimic3", 1234)
    return ds, gen.make_scenario(ds, n_active_features=5, n_aligned=500,
                                 seed=99)


def test_dataset():
    ds, _ = scenario()
    assert ds["x"].shape == (20000, 15) and ds["n_classes"] == 4
    assert digest(ds["x"], ds["y"], ds["ids"]) == "4f3f2e572f9e91e4"


def test_scenario():
    _, sc = scenario()
    assert sc["xa"].shape == (10250, 5) and sc["xp"].shape == (10250, 10)
    assert np.array_equal(sc["ids_a"][:500], sc["ids_p"][:500])
    assert digest(sc["xa"], sc["ids_a"], sc["ya"], sc["xp"],
                  sc["ids_p"]) == "453f9bc3cd404276"


def test_request_stream():
    _, sc = scenario()
    reqs = gen.make_request_stream(sc["xa"], sc["ids_a"], 50, seed=7,
                                   max_rows=16, p_known=0.5)
    assert all(1 <= len(x) <= 16 and len(x) == len(i) for x, i in reqs)
    assert digest(*[a for r in reqs for a in r]) == "2173549999834a02"


def test_poisson_arrivals():
    t = gen.poisson_arrivals(100, 300.0, seed=3)
    assert np.all(np.diff(t) > 0)
    assert digest(t) == "3af746693c0dacfc"


def test_party_block():
    x = gen.make_party(5000, n_features=16, n_latent=8, party=3, seed=11,
                       noise=0.5, block_rows=2048)
    assert x.shape == (5000, 16)
    assert digest(np.asarray(x)) == "7c8b7e5f9caba902"


def test_derive_takes_large_seeds():
    assert gen.derive(2**33 + 17, 3, 1, 2) == 1610879034
    assert gen.derive(5, 1) != gen.derive(5, 2)
