"""CPU checks of the signal shape as data: the benchmark's generator makes
the paper's format bit for bit as it did when the shape was fixed in
code, makes and the reference featurizes a 23-channel montage, and a
configuration whose ``signal`` the program cannot serve is refused
before anything compiles."""

from __future__ import annotations

import copy
import hashlib
import json
import pathlib
import sys

import pytest

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import cell, compiles, eeg, program, reference  # noqa: E402

PAPER = eeg.Signal(channels=3, window_samples=2048, chunk_windows=60,
                   sample_rate_hz=256)
MONTAGE23 = PAPER._replace(channels=23)
KEY_SEED = 2**33 + 5

# sha256 (first 16 hex digits) of each array at the paper's format, as the
# generator made it with 3 channels, 2048 samples and 60-window chunks
# fixed in code.
PAPER_SHA = {
    "windows0": "b3aea9eeafacf874",
    "windows1": "4d070d067e41ce33",
    "windows2": "8ed742e21198bf59",
    "training_set": "123af8634ccc7938/753c86d059be7b6d",
    "timeline": "fe7bf2de93d21c9b/be6d81d607b539ee",
    "chunk_pool": "a0088996279d6764",
}


def _sha(*arrays) -> str:
    return "/".join(
        hashlib.sha256(np.ascontiguousarray(np.asarray(a)).tobytes())
        .hexdigest()[:16] for a in arrays)


def _make(name: str, sig: eeg.Signal):
    key = eeg.key_from_seed(KEY_SEED)
    if name.startswith("windows"):
        state = int(name[-1])
        return (jax.jit(lambda k, p: eeg.windows(k, p, state, 4, sig))(
            key, jnp.int32(3)),)
    if name == "training_set":
        return eeg.training_set(key, jnp.int32(4), n_inter=60, n_pre=60,
                                sig=sig)
    if name == "timeline":
        return eeg.timeline(key, jnp.int32(5), n_inter_chunks=1, sig=sig)
    return (eeg.chunk_pool(key, 2, 1, 21, sig),)


@pytest.mark.parametrize("name", sorted(PAPER_SHA))
def test_the_paper_format_is_made_bit_for_bit_as_before(name):
    assert _sha(*_make(name, PAPER)) == PAPER_SHA[name]


def test_both_configurations_state_the_paper_format():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert eeg.Signal.of(cfg) == PAPER, c["name"]
    # The 48-minute preictal record, 360 windows of the paper's format.
    assert PAPER.preictal_chunks == 6


def _eigh_shapes(jaxpr) -> list:
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "eigh":
            out.append(eqn.invars[0].aval.shape)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += _eigh_shapes(inner)
    return out


def test_a_23_channel_montage_is_made_and_featurized_by_the_reference():
    wins, labels = _make("training_set", MONTAGE23)
    assert wins.shape == (120, 23, 2048) and labels.shape == (120,)
    assert bool(jnp.all(jnp.isfinite(wins)))
    # Every channel has rhythms of its own.
    corr = np.corrcoef(np.asarray(wins[0]))
    assert np.max(np.abs(corr - np.eye(23))) < 0.99
    chunks = MONTAGE23.as_chunks(wins)[:1]
    feats = reference.chunk_features(chunks)
    assert feats.shape == (1, 60, 96 * 23)
    assert bool(jnp.all(jnp.isfinite(feats)))
    # MSPCA's covariance at each of its six scales is 60 x 23 wide.
    shapes = _eigh_shapes(jax.make_jaxpr(reference.chunk_features)(chunks).jaxpr)
    assert [s[-2:] for s in shapes] == [(60 * 23, 60 * 23)] * 6


def _montage23(workload: str) -> tuple:
    bench, entry, cfg, traffic = cell.load_spec(ROOT, workload)
    cfg = copy.deepcopy(cfg)
    cfg["signal"]["channels"] = 23
    return cfg, traffic


@pytest.mark.parametrize("workload", ["serve.reconnect", "train.mapreduce4"])
def test_a_configuration_the_program_cannot_serve_is_refused_before_compiling(
        workload):
    # What the drivers import before they build anything.
    import repro.serving  # noqa: F401
    from repro.launch import mesh  # noqa: F401
    from repro.signal import eeg_data, pipeline  # noqa: F401

    cfg, traffic = _montage23(workload)
    driver = cell.DRIVERS[cfg["kind"]]
    with compiles.Counter() as counter, pytest.raises(ValueError) as err:
        driver.setup(cfg, traffic, 2**35 + 9, jax.devices()[:1], cell.log)
    assert "signal.channels=23" in str(err.value)
    assert "PipelineConfig has no field 'channels'" in str(err.value)
    assert counter.counts == {"compiles": 0, "cache_loads": 0, "traces": 0}


def test_signal_keys_that_name_a_program_field_are_passed():
    from repro.signal.pipeline import PipelineConfig

    paper = PAPER._asdict()
    # Today no key names a field, and the paper's format passes unchanged.
    assert program.signal_fields(paper, PipelineConfig._fields) == {}
    # A program with a ``channels`` field takes the montage from the file.
    fields = PipelineConfig._fields + ("channels",)
    assert program.signal_fields(dict(paper, channels=23), fields) == {
        "channels": 23}
    for key, value in (("sample_rate_hz", 400), ("window_samples", 4096),
                       ("chunk_windows", 30), ("montage", "bipolar")):
        with pytest.raises(ValueError, match=f"signal.{key}="):
            program.signal_fields(dict(paper, **{key: value}),
                                  PipelineConfig._fields)
