"""Simulator-config tables: yaml fields, env-over-yaml precedence, and
the feature-exclusivity rule (reference: simulator/config/config.go —
env overrides per field at :148-159, exclusivity at :94-96, initial
scheduler config load at :232-257)."""

import re
from pathlib import Path

import pytest
import yaml

from kube_scheduler_simulator_tpu.config.config import (
    SimulatorConfiguration,
    load_config,
)


@pytest.fixture
def clean_env(monkeypatch):
    for var in ("PORT", "KUBE_APISERVER_URL", "KUBE_SCHEDULER_SIMULATOR_ETCD_URL",
                "CORS_ALLOWED_ORIGIN_LIST", "KUBE_SCHEDULER_CONFIG_PATH",
                "EXTERNAL_IMPORT_ENABLED", "RESOURCE_SYNC_ENABLED",
                "REPLAYER_ENABLED", "RECORD_FILE_PATH",
                "EXTERNAL_SCHEDULER_ENABLED"):
        monkeypatch.delenv(var, raising=False)
    return monkeypatch


def _write(tmp_path, data):
    p = tmp_path / "config.yaml"
    p.write_text(yaml.safe_dump(data))
    return str(p)


def test_defaults_without_file(clean_env, tmp_path):
    cfg = load_config(str(tmp_path / "missing.yaml"))
    assert cfg.port == 1212
    assert not cfg.external_import_enabled
    assert not cfg.resource_sync_enabled
    assert not cfg.replayer_enabled
    assert cfg.cors_allowed_origin_list == []


def test_yaml_fields_load(clean_env, tmp_path):
    cfg = load_config(_write(tmp_path, {
        "port": 4000,
        "etcdURL": "http://etcd:2379",
        "kubeApiServerUrl": "http://api:3131",
        "corsAllowedOriginList": ["http://a", "http://b"],
        "kubeSchedulerConfigPath": "/tmp/sched.yaml",
        "recordFilePath": "/tmp/rec.jsonl",
        "externalSchedulerEnabled": True,
    }))
    assert cfg.port == 4000
    assert cfg.etcd_url == "http://etcd:2379"
    assert cfg.kube_api_server_url == "http://api:3131"
    assert cfg.cors_allowed_origin_list == ["http://a", "http://b"]
    assert cfg.kube_scheduler_config_path == "/tmp/sched.yaml"
    assert cfg.record_file_path == "/tmp/rec.jsonl"
    assert cfg.external_scheduler_enabled


def test_env_overrides_yaml(clean_env, tmp_path):
    clean_env.setenv("PORT", "5555")
    clean_env.setenv("CORS_ALLOWED_ORIGIN_LIST", "http://x,http://y")
    clean_env.setenv("RECORD_FILE_PATH", "/env/rec.jsonl")
    clean_env.setenv("REPLAYER_ENABLED", "true")
    cfg = load_config(_write(tmp_path, {
        "port": 4000,
        "corsAllowedOriginList": ["http://a"],
        "recordFilePath": "/yaml/rec.jsonl",
    }))
    assert cfg.port == 5555
    assert cfg.cors_allowed_origin_list == ["http://x", "http://y"]
    assert cfg.record_file_path == "/env/rec.jsonl"
    assert cfg.replayer_enabled


def test_env_bool_accepts_go_style_values(clean_env, tmp_path):
    for v, want in [("1", True), ("true", True), ("TRUE", True),
                    ("yes", True), ("0", False), ("false", False), ("", False)]:
        clean_env.setenv("EXTERNAL_IMPORT_ENABLED", v)
        cfg = load_config(str(tmp_path / "missing.yaml"))
        assert cfg.external_import_enabled is want, v


def test_env_false_overrides_yaml_true(clean_env, tmp_path):
    clean_env.setenv("RESOURCE_SYNC_ENABLED", "false")
    cfg = load_config(_write(tmp_path, {"resourceSyncEnabled": True}))
    assert not cfg.resource_sync_enabled


@pytest.mark.parametrize("pair", [
    {"externalImportEnabled": True, "resourceSyncEnabled": True},
    {"externalImportEnabled": True, "replayEnabled": True},
    {"resourceSyncEnabled": True, "replayEnabled": True},
])
def test_import_sync_replay_mutually_exclusive(clean_env, tmp_path, pair):
    with pytest.raises(ValueError, match="simultaneous"):
        load_config(_write(tmp_path, pair))


def test_replay_enabled_accepts_both_yaml_keys(clean_env, tmp_path):
    assert load_config(_write(tmp_path, {"replayEnabled": True})).replayer_enabled
    assert load_config(_write(tmp_path, {"replayerEnabled": True})).replayer_enabled


def test_initial_scheduler_config_loads_yaml(clean_env, tmp_path):
    sched = tmp_path / "sched.yaml"
    sched.write_text(yaml.safe_dump({
        "kind": "KubeSchedulerConfiguration",
        "profiles": [{"schedulerName": "my-scheduler"}],
    }))
    cfg = SimulatorConfiguration(kube_scheduler_config_path=str(sched))
    loaded = cfg.initial_scheduler_config()
    assert loaded["profiles"][0]["schedulerName"] == "my-scheduler"
    assert SimulatorConfiguration().initial_scheduler_config() is None


# ---------------------------------------------- the documented surface

_REPO = Path(__file__).resolve().parent.parent
_ENV_NAME = re.compile(r"KSS_TPU_[A-Z0-9_]+")


def _env_names_read():
    names = set()
    for src in (_REPO / "kube_scheduler_simulator_tpu").rglob("*.py"):
        names |= set(_ENV_NAME.findall(src.read_text()))
    return names


def _env_names_documented():
    doc = (_REPO / "docs" / "environment-variables.md").read_text()
    return set(_ENV_NAME.findall(doc))


@pytest.mark.parametrize("have, want, what", [
    (_env_names_documented, _env_names_read,
     "read by the package, missing from docs/environment-variables.md"),
    (_env_names_read, _env_names_documented,
     "documented, read nowhere in the package"),
], ids=["every_name_read_is_documented", "every_name_documented_is_read"])
def test_kss_tpu_env_names_match_their_document(have, want, what):
    """An option nobody can find, and a document of an option that is
    gone, are both repaired in the document."""
    assert sorted(want() - have()) == [], what


def test_every_makefile_target_runs_something_that_exists():
    text = (_REPO / "Makefile").read_text()
    ran = set(re.findall(r"\$\(PY\) ([\w/]+\.py)", text))
    ran |= set(re.findall(r"pytest (tests/\w+\.py)", text))
    ran |= {mod.replace(".", "/")
            for mod in re.findall(r"\$\(PY\) -m (tools\.\w+)", text)}
    assert len(ran) >= 9, ran
    missing = [r for r in sorted(ran)
               if not ((_REPO / r).is_file()
                       or (_REPO / (r + ".py")).is_file()
                       or (_REPO / r / "__main__.py").is_file())]
    assert missing == []
