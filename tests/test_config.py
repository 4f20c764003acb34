import json

import pytest

from storm_tpu.config import BatchConfig, Config, OffsetsConfig, SinkConfig


def test_defaults_mirror_reference_constants():
    # MainTopology.java:25-28 — 2 spouts / 4 inference / 2 sinks.
    cfg = Config()
    assert cfg.topology.spout_parallelism == 2
    assert cfg.topology.inference_parallelism == 4
    assert cfg.topology.sink_parallelism == 2
    # Reference freshness semantics (MainTopology.java:101-103).
    assert cfg.offsets.policy == "latest"
    assert cfg.offsets.max_behind == 0
    # KafkaBolt defaults (KafkaBolt.java:50-54): async, not fire-and-forget.
    assert cfg.sink.mode == "async"


def test_bucket_selection():
    b = BatchConfig(max_batch=64, buckets=(8, 16, 64))
    assert b.bucket_for(1) == 8
    assert b.bucket_for(9) == 16
    assert b.bucket_for(64) == 64
    assert b.bucket_for(1000) == 64


def test_buckets_normalized():
    b = BatchConfig(max_batch=32, buckets=(64, 8))
    assert b.buckets[-1] == 32
    assert 64 not in b.buckets


def test_apply_dict_and_overrides():
    cfg = Config.from_dict({"topology": {"inference_parallelism": 8}})
    assert cfg.topology.inference_parallelism == 8
    cfg.apply_overrides(["model.name=resnet20", "batch.max_batch=128"])
    assert cfg.model.name == "resnet20"
    assert cfg.batch.max_batch == 128


def test_unknown_key_rejected():
    with pytest.raises(KeyError):
        Config.from_dict({"topology": {"nope": 1}})
    with pytest.raises(KeyError):
        Config.from_dict({"nope": {}})


def test_removed_batch_continuous_is_an_unknown_key():
    """The ``continuous`` key of ``[batch]`` is gone with the per-task
    path it selected (docs/MIGRATION.md): a config that still sets it fails like any
    unknown key, and no alias remains on the dataclass."""
    from storm_tpu.config import BatchConfig

    with pytest.raises(KeyError, match="unknown config key 'continuous'"):
        Config.from_dict({"batch": {"continuous": False}})
    with pytest.raises(TypeError):
        BatchConfig(**{"continuous": True})
    assert not hasattr(BatchConfig(), "continuous")


def test_invalid_enum_values():
    with pytest.raises(ValueError):
        OffsetsConfig(policy="bogus")
    with pytest.raises(ValueError):
        SinkConfig(mode="bogus")


def test_load_json(tmp_path):
    p = tmp_path / "cfg.json"
    p.write_text(json.dumps({"broker": {"input_topic": "in-x"}}))
    cfg = Config.load(p)
    assert cfg.broker.input_topic == "in-x"


def test_load_toml(tmp_path):
    p = tmp_path / "cfg.toml"
    p.write_text('[model]\nname = "vit_b16"\nnum_classes = 1000\n')
    cfg = Config.load(p)
    assert cfg.model.name == "vit_b16"
    assert cfg.model.num_classes == 1000


def test_broker_config_validates_message_format():
    from storm_tpu.config import BrokerConfig

    assert BrokerConfig(message_format="v2").message_format == "v2"
    with pytest.raises(ValueError, match="message_format"):
        BrokerConfig(message_format="V2")
    with pytest.raises(ValueError, match="kind"):
        BrokerConfig(kind="rabbitmq")


def test_model_config_validates_weights():
    from storm_tpu.config import ModelConfig

    assert ModelConfig(weights="int8").weights == "int8"
    with pytest.raises(ValueError, match="weights"):
        ModelConfig(weights="int4")


def test_batch_config_max_inflight():
    from storm_tpu.config import BatchConfig

    assert BatchConfig().max_inflight == 2
    assert BatchConfig(max_inflight=4).max_inflight == 4
