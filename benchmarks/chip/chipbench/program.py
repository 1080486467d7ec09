"""The system under test as the configuration files state it: the
program's ``PipelineConfig`` built from a configuration's numbers, and
the served forest lowered into the program's ``ScoringProgram``."""

from __future__ import annotations

from chipbench import reference

# What the program serves where its ``PipelineConfig`` has no field for a
# key of the configuration's ``signal`` block: the constant of
# ``repro.signal.eeg_data`` that fixes it.
PROGRAM_SIGNAL = {"channels": "N_CHANNELS", "window_samples": "WINDOW",
                  "chunk_windows": "WINDOWS_PER_MATRIX",
                  "sample_rate_hz": "FS"}


def signal_fields(signal: dict, fields) -> dict:
    """The ``signal`` keys to pass to the program's ``PipelineConfig``
    (whose fields are ``fields``). A key that names a field is passed; a
    key that names none has to equal what the program serves, else the
    configuration is refused before anything is built or compiled."""
    from repro.signal import eeg_data

    passed = {}
    for key, value in signal.items():
        if key in fields:
            passed[key] = value
            continue
        served = getattr(eeg_data, PROGRAM_SIGNAL.get(key, ""), None)
        if value != served:
            raise ValueError(
                f"configuration key signal.{key}={value!r}: the program's "
                f"PipelineConfig has no field {key!r}, and the program "
                f"serves {served!r} only")
    return passed


def pipeline_config(cfg: dict):
    """The program's ``PipelineConfig`` from a configuration file. The
    reference implements one front end and one alarm rule; a
    configuration that asks for another is refused, not compared."""
    from repro.core.rotation_forest import RotationForestConfig
    from repro.signal.pipeline import PipelineConfig

    shape = signal_fields(cfg["signal"], PipelineConfig._fields)
    p = cfg["pipeline"]
    fixed = dict(mspca_level=reference.MSPCA_LEVEL,
                 wpd_level=reference.WPD_LEVEL, wavelet="db4",
                 alarm_k=reference.ALARM_K, alarm_m=reference.ALARM_M,
                 overlap=0, denoise=True)
    for k, v in fixed.items():
        if p[k] != v:
            raise ValueError(f"configuration {k}={p[k]!r}: the reference "
                             f"implements {v!r} only")
    return PipelineConfig(
        wpd_level=p["wpd_level"], wavelet=p["wavelet"],
        mspca_level=p["mspca_level"], denoise=p["denoise"],
        forest=RotationForestConfig(**p["forest"]),
        alarm_k=p["alarm_k"], alarm_m=p["alarm_m"], overlap=p["overlap"],
        **shape,
    )


def scoring_program(served, pcfg):
    """The benchmark's served forest (``reference.Served``) as the
    program's ``ScoringProgram``."""
    from repro.core.decision_tree import TreeParams
    from repro.core.rotation_forest import RotationForestParams
    from repro.serving import ScoringProgram
    from repro.signal.pipeline import FittedPipeline

    f = served.forest
    trees = TreeParams(split_feature=f.feature, split_bin=served.split_bin,
                       leaf_probs=f.leaf, bin_edges=served.edges)
    fitted = FittedPipeline(RotationForestParams(f.rotation, trees),
                            served.mean, served.std)
    return ScoringProgram.from_fitted(fitted, pcfg)
