"""CLI e2e: a full rsync replication and a migration driven purely
through ``volsync`` verbs (the reference's CLI roles in the e2e tier —
kubectl-volsync/cmd + test-e2e CLI playbooks), plus parse-level and
relationship-file unit coverage (parse_test.go / relationship_test.go
analogues), plus the packaged operator runtime boot.
"""

import pathlib

import pytest

from volsync_tpu.cli import Relationship, RelationshipError, build_parser, run
from volsync_tpu.cli.relationship import TYPE_MIGRATION, TYPE_REPLICATION
from volsync_tpu.operator import OperatorRuntime, resolve_config


@pytest.fixture
def world(tmp_path):
    """Two operator stacks = two 'kubeconfig contexts' (the reference
    drives source and destination clusters the same way)."""
    src = OperatorRuntime({"storage_path": str(tmp_path / "src-storage"),
                           "metrics_port": 0}).start()
    dst = OperatorRuntime({"storage_path": str(tmp_path / "dst-storage"),
                           "metrics_port": 0}).start()
    yield {"source": src.cluster, "destination": dst.cluster}, tmp_path
    src.stop()
    dst.stop()


def _mk_pvc(cluster, name, files: dict):
    from volsync_tpu.api.common import ObjectMeta
    from volsync_tpu.cluster.objects import Volume, VolumeSpec

    vol = cluster.create(Volume(
        metadata=ObjectMeta(name=name, namespace="default"),
        spec=VolumeSpec(capacity=1 << 30)))
    root = pathlib.Path(vol.status.path)
    for rel, content in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_bytes(content)
    return root


def _cli(contexts, tmp_path, argv):
    lines = []
    rc = run(["--config-dir", str(tmp_path / "cfg")] + argv, contexts,
             out=lines.append)
    return rc, lines


def test_replication_end_to_end_via_cli(world, rng):
    contexts, tmp_path = world
    files = {"a.txt": b"alpha" * 500, "d/b.bin": rng.bytes(200_000)}
    _mk_pvc(contexts["source"], "app-data", files)

    assert _cli(contexts, tmp_path, ["replication", "create", "rel1"])[0] == 0
    rc, out = _cli(contexts, tmp_path, [
        "replication", "set-destination", "rel1",
        "--cluster", "destination", "--dest-name", "dest",
        "--copy-method", "Snapshot"])
    assert rc == 0, out
    rc, out = _cli(contexts, tmp_path, [
        "replication", "set-source", "rel1",
        "--cluster", "source", "--pvcname", "app-data"])
    assert rc == 0, out
    rc, out = _cli(contexts, tmp_path, ["replication", "sync", "rel1"])
    assert rc == 0, out

    # The destination cluster holds a synced latestImage snapshot (its
    # reconcile publishes the image asynchronously after the listener
    # Job completes).
    dst = contexts["destination"]
    assert dst.wait_for(lambda: (
        (rd := dst.try_get("ReplicationDestination", "default", "dest"))
        and rd.status and rd.status.latest_image is not None),
        timeout=30, poll=0.1)
    rd = dst.get("ReplicationDestination", "default", "dest")
    snap = dst.get("VolumeSnapshot", "default", rd.status.latest_image.name)
    restored = pathlib.Path(snap.status.bound_content)
    for rel, content in files.items():
        assert (restored / rel).read_bytes() == content

    # schedule writes a cron trigger through the CLI
    rc, _ = _cli(contexts, tmp_path,
                 ["replication", "schedule", "rel1", "*/5 * * * *"])
    assert rc == 0
    src_cr = contexts["source"].get("ReplicationSource", "default",
                                    "volsync-rel1")
    assert src_cr.spec.trigger.schedule == "*/5 * * * *"

    # delete removes the labeled objects in BOTH clusters + the file
    rc, _ = _cli(contexts, tmp_path, ["replication", "delete", "rel1"])
    assert rc == 0
    assert contexts["source"].try_get("ReplicationSource", "default",
                                      "volsync-rel1") is None
    assert dst.try_get("ReplicationDestination", "default", "dest") is None
    assert not (tmp_path / "cfg" / "rel1.json").exists()


def test_migration_local_push_via_cli(world, rng):
    contexts, tmp_path = world
    payload = {"big.bin": rng.bytes(150_000), "sub/x.txt": b"hello"}
    local = tmp_path / "workstation"
    for rel, content in payload.items():
        p = local / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_bytes(content)

    rc, out = _cli(contexts, tmp_path, [
        "migration", "create", "mig1", "--cluster", "destination",
        "--pvcname", "migrated", "--capacity", str(1 << 30)])
    assert rc == 0, out
    rc, out = _cli(contexts, tmp_path,
                   ["migration", "rsync", "mig1", str(local)])
    assert rc == 0, out

    dst = contexts["destination"]
    vol = dst.get("Volume", "default", "migrated")
    root = pathlib.Path(vol.status.path)
    for rel, content in payload.items():
        assert (root / rel).read_bytes() == content

    rc, _ = _cli(contexts, tmp_path, ["migration", "delete", "mig1"])
    assert rc == 0
    assert dst.try_get("ReplicationDestination", "default",
                       "volsync-mig-mig1") is None


def test_parse_tree(tmp_path):
    p = build_parser()
    args = p.parse_args(["replication", "set-destination", "r",
                         "--dest-name", "d", "--copy-method", "Clone"])
    assert args.group == "replication" and args.verb == "set-destination"
    assert args.copy_method == "Clone"
    args = p.parse_args(["migration", "rsync", "m", "/some/dir"])
    assert args.verb == "rsync" and args.source_dir == "/some/dir"
    with pytest.raises(SystemExit):
        p.parse_args(["replication", "set-destination", "r",
                      "--copy-method", "Bogus", "--dest-name", "d"])


@pytest.mark.parametrize("verb", ["lint", "trace", "repair", "scrub",
                                  "repack"])
def test_a_delegated_verb_resolves(verb, monkeypatch, capsys):
    """``run`` imports a delegated verb's module only when the verb is
    called, so a module that has gone breaks nothing until then: call
    each, by its cheapest no-op, before the operator's boot."""
    import volsync_tpu.operator as operator_module
    from volsync_tpu.cli.main import main

    def boot(*_args, **_kw):
        raise AssertionError("the verb booted the operator runtime")

    monkeypatch.setattr(operator_module, "OperatorRuntime", boot)
    with pytest.raises(SystemExit) as done:
        main([verb, "--help"])
    assert done.value.code == 0
    assert f"usage: volsync {verb}" in capsys.readouterr().out


def test_session_is_no_verb(capsys):
    with pytest.raises(SystemExit) as refused:
        run(["session", "status"], {})
    assert refused.value.code == 2
    assert "invalid choice: 'session'" in capsys.readouterr().err


def test_relationship_files(tmp_path):
    rel = Relationship.create(tmp_path, "r1", TYPE_REPLICATION)
    rel.data["x"] = 1
    rel.save()
    loaded = Relationship.load(tmp_path, "r1", TYPE_REPLICATION)
    assert loaded.id == rel.id and loaded.data == {"x": 1}
    with pytest.raises(RelationshipError):
        Relationship.create(tmp_path, "r1", TYPE_REPLICATION)  # exists
    with pytest.raises(RelationshipError):
        Relationship.load(tmp_path, "r1", TYPE_MIGRATION)  # wrong type
    with pytest.raises(RelationshipError):
        Relationship.load(tmp_path, "nope", TYPE_REPLICATION)


def test_operator_config_precedence(monkeypatch):
    """Flag > env > default (the viper layering, main.go:105-128)."""
    cfg = resolve_config()
    assert cfg["metrics_port"] == 8080
    monkeypatch.setenv("VOLSYNC_METRICS_PORT", "9999")
    monkeypatch.setenv("VOLSYNC_MOVERS", "restic")
    cfg = resolve_config()
    assert cfg["metrics_port"] == 9999
    assert cfg["movers"] == "restic"
    from volsync_tpu.operator import build_parser as op_parser

    args = op_parser().parse_args(["--metrics-port", "7777"])
    cfg = resolve_config(args)
    assert cfg["metrics_port"] == 7777  # flag wins over env


def test_operator_runtime_boot(tmp_path):
    """The packaged process wires movers, metrics, and probes."""
    import urllib.request

    rt = OperatorRuntime({"storage_path": str(tmp_path / "s"),
                          "metrics_port": -1,
                          "movers": "restic,rsync"}).start()
    try:
        assert rt.catalog.names() == ["restic", "rsync"]
        port = rt.metrics_server.port
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read()
        assert b"volsync_" in body
        ready = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/readyz", timeout=5)
        assert ready.status == 200
    finally:
        rt.stop()
