"""Build-time capability matrix (api.build.check_capabilities).

Every unsupported spec combination must fail at build time with the ONE
message shape ``unsupported spec combination: {combo} requires {need} —
{why}`` — never a step-build NotImplementedError deep in core.engine.
"""
import dataclasses

import pytest

from repro.api import (
    ClassesCfg,
    FaultsCfg,
    PrivacyCfg,
    build,
    quickstart_spec,
)
from repro.api.build import check_capabilities
from repro.api.spec import RunCfg, ShardingCfg

MSG = "unsupported spec combination"


def qs(**run_over):
    spec = quickstart_spec(rounds=2)
    if run_over:
        spec = spec.replace(run=dataclasses.replace(spec.run, **run_over))
    return spec


def test_supported_combinations_pass():
    check_capabilities(qs())
    check_capabilities(qs(engine="b"))
    check_capabilities(qs(sharding=ShardingCfg(data=2)))
    check_capabilities(qs(staleness=1))
    check_capabilities(qs(sharding=ShardingCfg(data=2), staleness=(1, 0, 0)))
    # a NOISELESS privacy section composes to nothing: sharding-safe
    check_capabilities(
        qs(sharding=ShardingCfg(data=2)).replace(
            privacy=PrivacyCfg(noise_multiplier=0.0)
        )
    )


@pytest.mark.parametrize(
    "section",
    [
        dict(classes=ClassesCfg(num_classes=2)),
        dict(privacy=PrivacyCfg(noise_multiplier=1.0)),
        dict(faults=FaultsCfg(crash_rate=0.1)),
    ],
    ids=["classes", "privacy", "faults"],
)
def test_engine_b_feature_matrix(section):
    spec = qs(engine="b").replace(**section)
    with pytest.raises(ValueError, match=MSG) as e:
        build(spec)
    assert 'engine="a"' in str(e.value)


def test_engine_b_rejects_sharding_and_staleness():
    with pytest.raises(ValueError, match=f"{MSG}: sharding"):
        build(qs(engine="b", sharding=ShardingCfg(data=2)))
    with pytest.raises(ValueError, match=f"{MSG}: staleness"):
        build(qs(engine="b", staleness=1))


@pytest.mark.parametrize(
    "feature_over",
    [dict(sharding=ShardingCfg(data=2)), dict(staleness=1)],
    ids=["sharding", "staleness"],
)
def test_sharded_async_feature_matrix(feature_over):
    feature = next(iter(feature_over))
    with pytest.raises(ValueError, match=f"{MSG}: {feature} × privacy"):
        build(qs(**feature_over).replace(
            privacy=PrivacyCfg(noise_multiplier=1.0)
        ))
    with pytest.raises(ValueError, match=f"{MSG}: {feature} × classes"):
        build(qs(**feature_over).replace(classes=ClassesCfg(num_classes=2)))
    with pytest.raises(ValueError, match=f"{MSG}: {feature} × faults"):
        build(qs(**feature_over).replace(faults=FaultsCfg(crash_rate=0.1)))
    with pytest.raises(ValueError, match=f'{MSG}: {feature} × mode="control"'):
        build(qs(mode="control", **feature_over))


def test_message_shape_is_uniform():
    with pytest.raises(ValueError) as e:
        build(qs(engine="b").replace(faults=FaultsCfg(crash_rate=0.1)))
    msg = str(e.value)
    assert msg.startswith("unsupported spec combination: ")
    assert " requires " in msg and " — " in msg


@pytest.mark.parametrize("feature", ["privacy", "class_members"])
def test_the_mesh_step_refuses_what_the_matrix_refuses(feature):
    # the engine's backstop for direct callers, in the matrix's words
    from repro.core import build_train_step_a
    from repro.core.tiers import default_plan
    from repro.launch.mesh import make_debug_mesh
    from repro.optim import sgd

    combo = "privacy" if feature == "privacy" else "classes"
    with pytest.raises(ValueError, match=f"{MSG}: sharding × {combo} requires"):
        build_train_step_a(None, default_plan(4, 4), sgd(0.1),
                           mesh=make_debug_mesh(data=1, model=1),
                           **{feature: object()})
