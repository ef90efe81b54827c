"""The generator: a configuration's reference and a traffic's reads are the
same for the same seed, at the stated composition and repeat content."""
import json

import numpy as np
import pytest

import gen
import run

CONFIGS = ("grch38_chr21", "dm6")


def _config(name: str, bases: int) -> dict:
    conf = json.loads((run.HERE / "configs" / f"{name}.json").read_text())
    conf["reference_bases"] = bases
    return conf


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_is_fixed_by_its_seed(name):
    conf = _config(name, 400_000)
    a, b = gen.make_reference(conf), gen.make_reference(conf)
    assert a.dtype == np.uint8 and len(a) == 400_000
    assert np.array_equal(a, b)
    assert int(a.max()) <= 3
    gc = float(np.isin(a, (1, 2)).mean())
    want = conf["base_freq"]["C"] + conf["base_freq"]["G"]
    assert abs(gc - want) < 0.02


@pytest.mark.parametrize("name", CONFIGS)
def test_repeats_raise_kmer_multiplicity(name):
    """Repeat copies share k-mers: the 12-mers of the configured reference
    repeat far more often than those of its background alone (at 2 Mb a
    fly transposon family keeps a few copies, a human class hundreds)."""
    conf = _config(name, 2_000_000)
    plain = dict(conf, repeats=None, tandem_repeats=None,
                 segmental_dups=None)

    def top_share(ref):
        k = 12
        codes = np.zeros(len(ref) - k + 1, np.uint32)
        for j in range(k):
            codes = (codes << 2) | ref[j:len(ref) - k + 1 + j]
        _, counts = np.unique(codes, return_counts=True)
        return float(counts[counts > 4].sum()) / len(codes)

    assert top_share(gen.make_reference(conf)) > 0.01
    assert top_share(gen.make_reference(plain)) < 0.001


def test_classes_fill_their_share():
    """With no divergence, every copy of a class is its consensus fragment:
    the class's bases are laid down whole, without overlap."""
    conf = _config("grch38_chr21", 1_000_000)
    cls = dict(conf["repeats"]["classes"][0], divergence=[0.0, 0.0],
               families=1)
    rep = dict(conf["repeats"], classes=[cls])
    ref = gen.make_reference(dict(conf, repeats=rep, tandem_repeats=None,
                                  segmental_dups=None))
    scale = 1_000_000 / (rep["table_genome_mb"] * 1e6)
    n = int(round(cls["copies"] * scale))
    length = int(round(cls["mb"] * 1e6 / cls["copies"]))
    assert n > 0
    rng = np.random.default_rng([conf["reference_seed"], 1])
    lut = gen._base_lut(conf["base_freq"])
    background = gen._bases(rng, lut, 1_000_000)
    changed = float((ref != background).mean())
    # a replaced base differs from the background's with probability ~0.7
    assert changed == pytest.approx(0.7 * n * length / 1_000_000, rel=0.15)


@pytest.mark.parametrize("traffic", ("se150", "pe150"))
def test_reads_are_fixed_by_the_seed(traffic):
    t = json.loads((run.HERE / "traffic" / f"{traffic}.json").read_text())
    t.update(reads=256, pairs=128)
    ref = gen.make_reference(_config("grch38_chr21", 200_000))
    a, b = gen.sample(ref, t, 2**31 + 5), gen.sample(ref, t, 2**31 + 5)
    assert np.array_equal(a.reads1, b.reads1)
    assert np.array_equal(a.pos1, b.pos1)
    assert a.reads1.shape[1] == t["read_len"]
    if a.paired:
        lo = np.minimum(a.pos1, a.pos2)
        hi = np.maximum(a.pos1, a.pos2) + t["read_len"]
        frag = hi - lo
        assert abs(float(np.median(frag)) - t["fragment_mean"]) < 10
