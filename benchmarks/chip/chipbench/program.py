"""The system under test as the configuration files state it: the
program's ``PipelineConfig`` built from a configuration's numbers, and
the served forest lowered into the program's ``ScoringProgram``."""

from __future__ import annotations

from chipbench import reference


def pipeline_config(cfg: dict):
    """The program's ``PipelineConfig`` from a configuration file. The
    reference implements one front end and one alarm rule; a
    configuration that asks for another is refused, not compared."""
    from repro.core.rotation_forest import RotationForestConfig
    from repro.signal.pipeline import PipelineConfig

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
