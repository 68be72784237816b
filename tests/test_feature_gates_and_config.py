"""Feature gates + dynamic config hot-reload."""

import json
import time

import pytest

from production_stack_tpu.router.dynamic_config import (
    DynamicConfigWatcher,
    DynamicRouterConfig,
)
from production_stack_tpu.router.experimental.feature_gates import (
    SEMANTIC_CACHE_GATE,
    FeatureGates,
)
from production_stack_tpu.router.routing.logic import (
    LeastLoadedPolicy,
    RoundRobinPolicy,
    get_routing_logic,
    initialize_routing_logic,
)
from production_stack_tpu.router.service_discovery import (
    get_service_discovery,
    initialize_service_discovery,
)
from production_stack_tpu.router.stats.request_stats import (
    initialize_request_stats_monitor,
)


def test_feature_gates_parse():
    gates = FeatureGates("SemanticCache=true")
    assert gates.enabled(SEMANTIC_CACHE_GATE)
    assert not gates.enabled("PIIDetection")


def test_feature_gates_reject_unknown():
    with pytest.raises(ValueError):
        FeatureGates("NoSuchGate=true")
    with pytest.raises(ValueError):
        FeatureGates("SemanticCache")


def test_dynamic_config_parses_string_and_list_backends():
    config = DynamicRouterConfig.from_json(json.dumps({
        "service_discovery": "static",
        "routing_logic": "llq",
        "static_backends": "http://a:1,http://b:2",
        "static_models": ["m1", "m2"],
    }))
    assert config.static_backends == ["http://a:1", "http://b:2"]
    assert config.static_models == ["m1", "m2"]


def test_dynamic_config_watcher_applies_changes(tmp_path):
    initialize_request_stats_monitor(60.0)
    initialize_service_discovery("static", urls=["http://old:1"])
    initialize_routing_logic("roundrobin")
    assert isinstance(get_routing_logic(), RoundRobinPolicy)

    config_path = tmp_path / "dynamic.json"
    config_path.write_text(json.dumps({
        "service_discovery": "static",
        "routing_logic": "llq",
        "static_backends": "http://new:2",
        "static_models": "modelA",
    }))
    watcher = DynamicConfigWatcher(str(config_path), poll_interval_s=3600)
    try:
        # The watcher's own thread applies a file that is there at
        # start-up, whenever it first gets the CPU: wait for that tick
        # to end (then it sleeps its 3600 s), so that it neither takes
        # a later change from under this thread's own call nor applies
        # the first text over the last.
        deadline = time.monotonic() + 30.0
        while (watcher.get_current_config() is None
               and time.monotonic() < deadline):
            time.sleep(0.005)
        assert watcher.get_current_config() is not None
        assert watcher.check_and_apply() is False
        eps = get_service_discovery().get_endpoint_info()
        assert [ep.url for ep in eps] == ["http://new:2"]
        assert eps[0].model_names == ["modelA"]
        assert isinstance(get_routing_logic(), LeastLoadedPolicy)

        # Unchanged file is a no-op.
        assert watcher.check_and_apply() is False

        # Changed file reapplies.
        config_path.write_text(json.dumps({
            "service_discovery": "static",
            "routing_logic": "roundrobin",
            "static_backends": "http://third:3",
        }))
        assert watcher.check_and_apply() is True
        assert isinstance(get_routing_logic(), RoundRobinPolicy)
    finally:
        watcher.close()


def test_dynamic_config_watcher_survives_bad_json(tmp_path):
    initialize_request_stats_monitor(60.0)
    initialize_service_discovery("static", urls=["http://keep:1"])
    initialize_routing_logic("roundrobin")
    config_path = tmp_path / "dynamic.json"
    config_path.write_text("{not json")
    watcher = DynamicConfigWatcher(str(config_path), poll_interval_s=3600)
    try:
        assert watcher.check_and_apply() is False
        eps = get_service_discovery().get_endpoint_info()
        assert [ep.url for ep in eps] == ["http://keep:1"]
    finally:
        watcher.close()
