"""Pluggable sources/sinks, image ingest, config, load_op, batch_load."""

import os
import struct

import numpy as np
import pytest

from scanner_tpu import (CacheMode, Client, NamedStream, NamedVideoStream,
                         PerfParams)
from scanner_tpu.storage import FilesStream
import scanner_tpu.kernels
from scanner_tpu import video as scv


@pytest.fixture(scope="module")
def sc(tmp_path_factory):
    root = tmp_path_factory.mktemp("ext")
    vid = str(root / "v.mp4")
    scv.synthesize_video(vid, num_frames=24, width=64, height=48, fps=24)
    client = Client(db_path=str(root / "db"))
    client.ingest_videos([("test1", vid)])
    yield client, str(root)
    client.stop()


def test_files_source_and_sink(sc):
    client, root = sc
    # write input rows as files
    src_dir = os.path.join(root, "files_in")
    os.makedirs(os.path.join(src_dir, "nums"))
    for i in range(10):
        with open(os.path.join(src_dir, "nums", f"{i:08d}.bin"), "wb") as f:
            f.write(struct.pack("<q", i * 3))
    in_stream = FilesStream("nums", src_dir)
    assert in_stream.len() == 10

    import scanner_tpu
    from typing import Any

    @scanner_tpu.register_op(name="TripleUp")
    class TripleUp(scanner_tpu.Kernel):
        def execute(self, x: bytes) -> bytes:
            (v,) = struct.unpack("<q", x)
            return struct.pack("<q", v + 1)

    data = client.io.Input([in_stream])
    up = client.ops.TripleUp(x=data)
    out_stream = FilesStream("nums_out", os.path.join(root, "files_out"))
    client.run(client.io.Output(up, [out_stream]), PerfParams.manual(4, 4),
               cache_mode=CacheMode.Overwrite, show_progress=False)
    got = [struct.unpack("<q", b)[0] for b in out_stream.load()]
    assert got == [i * 3 + 1 for i in range(10)]


def test_files_to_table_and_back(sc):
    client, root = sc
    # video input -> files sink of pickled histograms
    frame = client.io.Input([NamedVideoStream(client, "test1")])
    hist = client.ops.Histogram(frame=frame)
    out = FilesStream("hists", os.path.join(root, "files_out2"),
                      codec="pickle")
    client.run(client.io.Output(hist, [out]), PerfParams.manual(8, 8),
               cache_mode=CacheMode.Overwrite, show_progress=False)
    rows = list(out.load())
    assert len(rows) == 24 and rows[0][0].shape == (16,)


def test_image_ingest_and_pipeline(sc, tmp_path):
    client, root = sc
    from PIL import Image
    paths = []
    for i in range(5):
        p = str(tmp_path / f"img{i}.png")
        Image.fromarray(scv.frame_pattern(i, 48, 64)).save(p)
        paths.append(p)
    client.ingest_images("stills", paths)
    t = client.table("stills")
    assert t.num_rows() == 5
    # through the engine
    frame = client.io.Input([NamedVideoStream(client, "stills")])
    hist = client.ops.Histogram(frame=frame)
    out = NamedStream(client, "still_hists")
    client.run(client.io.Output(hist, [out]), PerfParams.manual(4, 4),
               cache_mode=CacheMode.Overwrite, show_progress=False)
    rows = list(out.load())
    assert len(rows) == 5
    assert int(rows[0][0].sum()) == 64 * 48
    # encode kernel roundtrip
    frame = client.io.Input([NamedVideoStream(client, "stills")])
    enc = client.ops.ImageEncode(frame=frame, format="png")
    out2 = NamedStream(client, "still_pngs")
    client.run(client.io.Output(enc, [out2]), PerfParams.manual(4, 4),
               cache_mode=CacheMode.Overwrite, show_progress=False)
    blobs = list(out2.load())
    assert blobs[0][:8] == b"\x89PNG\r\n\x1a\n"


def test_config_roundtrip(tmp_path):
    from scanner_tpu.config import Config, default_config, dump_toml
    p = str(tmp_path / "cfg.toml")
    with open(p, "w") as f:
        f.write(dump_toml(default_config()))
    cfg = Config(p, db_path=str(tmp_path / "db"))
    assert cfg.storage_type == "posix"
    assert cfg.db_path == str(tmp_path / "db")
    assert cfg.master_address is None  # default: in-process execution
    # explicit master in config selects cluster mode, localhost included
    with open(p, "w") as f:
        f.write('[network]\nmaster = "localhost"\nmaster_port = 5055\n')
    cfg = Config(p)
    assert cfg.master_address == "localhost:5055"
    # legacy combined key also accepted
    with open(p, "w") as f:
        f.write('[network]\nmaster_address = "10.0.0.5:5000"\n')
    assert Config(p).master_address == "10.0.0.5:5000"


def test_load_op(sc, tmp_path):
    client, root = sc
    mod = tmp_path / "user_ops.py"
    mod.write_text(
        "from scanner_tpu import Kernel, register_op\n"
        "@register_op(name='UserDouble')\n"
        "class UserDouble(Kernel):\n"
        "    def execute(self, x: bytes) -> bytes:\n"
        "        return x + x\n")
    client.load_op(str(mod))
    from scanner_tpu.graph.ops import registry
    assert registry.has("UserDouble")


def test_batch_load(sc):
    client, root = sc
    client.new_table("bl1", ["c"], [[b"a"], [b"b"]], overwrite=True)
    client.new_table("bl2", ["c"], [[b"x"]], overwrite=True)
    s1, s2 = NamedStream(client, "bl1"), NamedStream(client, "bl2")
    res = client.batch_load([s1, s2])
    assert res == [[b"a", b"b"], [b"x"]]


def test_deploy_manifests():
    from scanner_tpu.deploy import (CloudConfig, Cluster, ClusterConfig,
                                    MachineType)
    cfg = ClusterConfig(id="sc", num_workers=4,
                        worker=MachineType(tpu_type="v5litepod-4"))
    cluster = Cluster(CloudConfig(project="p"), cfg)
    by_kind = {(m["kind"], m["metadata"]["name"]): m
               for m in cluster.manifests()}
    assert ("Deployment", "sc-master") in by_kind
    assert ("ConfigMap", "sc-config") in by_kind
    workers = by_kind[("StatefulSet", "sc-worker")]
    assert workers["spec"]["replicas"] == 4  # single-host slice: 1 pod each
    limits = workers["spec"]["template"]["spec"]["containers"][0][
        "resources"]["limits"]
    assert limits["google.com/tpu"] == "4"
    # SIGTERM drain window (Worker.drain, docs/robustness.md): pods get
    # the configured grace period before the SIGKILL follow-up
    assert workers["spec"]["template"]["spec"][
        "terminationGracePeriodSeconds"] == cfg.termination_grace_period
    assert cfg.price_per_hour() > 0
    assert "sc-master" in cluster.manifests_json()
    toml = by_kind[("ConfigMap", "sc-config")]["data"]["scanner_tpu.toml"]
    assert 'type = "posix"' in toml


def test_deploy_multihost_slice():
    """A v5litepod-8 slice spans 2 hosts: each slice is its OWN
    StatefulSet pinned to a dedicated per-slice node pool (nodeSelector
    gke-nodepool + gke-tpu-topology) so a jax.distributed coordinator
    group is guaranteed slice-coherent; in-slice rank = pod ordinal,
    coordinator at pod 0's headless-service DNS name."""
    import ast

    from scanner_tpu.deploy import (CloudConfig, Cluster, ClusterConfig,
                                    MachineType, tpu_hosts)
    assert tpu_hosts("v5litepod-8") == 2
    cfg = ClusterConfig(id="sc", num_workers=3,
                        worker=MachineType(tpu_type="v5litepod-8"),
                        db_path="gs://bkt/db")
    cluster = Cluster(CloudConfig(project="p"), cfg)
    by_kind = {(m["kind"], m["metadata"]["name"]): m
               for m in cluster.manifests()}
    for i in range(3):
        workers = by_kind[("StatefulSet", f"sc-worker-s{i}")]
        assert workers["spec"]["replicas"] == 2   # hosts per slice
        pod = workers["spec"]["template"]["spec"]
        # slice coherence: dedicated pool + declared physical topology
        assert pod["nodeSelector"]["cloud.google.com/gke-nodepool"] \
            == f"sc-tpu-{i}"
        assert pod["nodeSelector"]["cloud.google.com/gke-tpu-topology"] \
            == "2x4"
        payload = pod["containers"][0]["command"][2]
        ast.parse(payload)  # generated -c program must be valid python
        assert "num_processes=2" in payload
        assert f"sc-worker-s{i}-0.sc-workers:8476" in payload
        # in-slice rank comes straight from the pod ordinal
        assert "rsplit('-', 1)[1]" in payload
    # headless service for stable pod DNS
    svc = by_kind[("Service", "sc-workers")]
    assert svc["spec"]["clusterIP"] == "None"
    # gs:// db selects the gcs backend in the ConfigMap
    toml = by_kind[("ConfigMap", "sc-config")]["data"]["scanner_tpu.toml"]
    assert 'type = "gcs"' in toml


def test_deploy_metrics_port_wiring():
    """ClusterConfig.metrics_port threads the live-telemetry endpoint
    through the manifests: start_master/start_worker args, exposed
    container ports, and the ConfigMap toml — and stays fully absent at
    the default (telemetry serving is opt-in, docs/observability.md)."""
    import ast

    from scanner_tpu.deploy import (CloudConfig, Cluster, ClusterConfig,
                                    MachineType)

    def manifests(port):
        cfg = ClusterConfig(id="sc", num_workers=2,
                            worker=MachineType(tpu_type="v5litepod-4"),
                            metrics_port=port)
        cluster = Cluster(CloudConfig(project="p"), cfg)
        return {(m["kind"], m["metadata"]["name"]): m
                for m in cluster.manifests()}

    on = manifests(9090)
    mc = on[("Deployment", "sc-master")]["spec"]["template"]["spec"][
        "containers"][0]
    ast.parse(mc["command"][2])
    assert "metrics_port=9090" in mc["command"][2]
    assert {"containerPort": 9090, "name": "metrics"} in mc["ports"]
    wc = on[("StatefulSet", "sc-worker")]["spec"]["template"]["spec"][
        "containers"][0]
    ast.parse(wc["command"][2])
    assert "metrics_port=9090" in wc["command"][2]
    assert {"containerPort": 9090, "name": "metrics"} in wc["ports"]
    # workers advertise their stable pod DNS so the master's GetMetrics
    # aggregation can dial them cross-host
    assert "advertise_host=os.environ['POD_NAME'] + '.sc-workers'" \
        in wc["command"][2]
    assert "metrics_port = 9090" in on[("ConfigMap", "sc-config")][
        "data"]["scanner_tpu.toml"]

    off = manifests(0)
    mc = off[("Deployment", "sc-master")]["spec"]["template"]["spec"][
        "containers"][0]
    assert "metrics_port" not in mc["command"][2]
    wc = off[("StatefulSet", "sc-worker")]["spec"]["template"]["spec"][
        "containers"][0]
    assert "metrics_port" not in wc["command"][2]
    assert "ports" not in wc


def test_compilation_cache_rule(tmp_path, monkeypatch):
    """One rule for where the persistent compilation cache lives
    (util/jaxenv.enable_compilation_cache): JAX_COMPILATION_CACHE_DIR
    set -> the code sets no directory; unset -> the fixed in-checkout
    path.  The three scanner-specific spellings are gone, and
    ClusterConfig.compilation_cache_dir reaches workers as the JAX
    variable."""
    import inspect

    from scanner_tpu.deploy import (CloudConfig, Cluster, ClusterConfig,
                                    MachineType)

    def manifests(cache):
        cfg = ClusterConfig(id="sc", num_workers=2,
                            worker=MachineType(tpu_type="v5litepod-4"),
                            compilation_cache_dir=cache)
        return {(m["kind"], m["metadata"]["name"]): m
                for m in Cluster(CloudConfig(project="p"), cfg).manifests()}

    on = manifests("gs://bkt/xla-cache")
    assert "compilation_cache_dir" not in on[("ConfigMap", "sc-config")][
        "data"]["scanner_tpu.toml"]
    wc = on[("StatefulSet", "sc-worker")]["spec"]["template"]["spec"][
        "containers"][0]
    assert {"name": "JAX_COMPILATION_CACHE_DIR",
            "value": "gs://bkt/xla-cache"} in wc["env"]
    # no directory named: still placed from outside, on pod scratch —
    # the image's installed package has no writable checkout
    from scanner_tpu.deploy import POD_CACHE_DIR
    wc = manifests("")[("StatefulSet", "sc-worker")]["spec"]["template"][
        "spec"]["containers"][0]
    assert {"name": "JAX_COMPILATION_CACHE_DIR",
            "value": POD_CACHE_DIR} in wc["env"]

    # no config key, no Client/Worker argument
    from scanner_tpu import Client
    from scanner_tpu.config import Config, default_config
    from scanner_tpu.engine.service import Worker
    assert "compilation_cache_dir" not in default_config()["perf"]
    assert not hasattr(Config, "compilation_cache_dir")
    for cls in (Client, Worker):
        assert "compilation_cache_dir" not in inspect.signature(
            cls.__init__).parameters

    import jax

    from scanner_tpu.util import jaxenv
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    try:
        # placed from outside: the code sets NO directory
        outside = str(tmp_path / "outside")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        assert jaxenv.enable_compilation_cache() == outside
        assert jax.config.jax_compilation_cache_dir == before
        assert not os.path.exists(outside)
        # small ladder executables are kept either way
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
        # unset: the fixed in-checkout path, the same on every call
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = jaxenv.enable_compilation_cache()
        assert fixed == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == fixed
        assert jaxenv.enable_compilation_cache() == fixed
        # not a checkout (an installed package under a read-only
        # root): the default cannot be created and the error names the
        # variable to set; with it set the same layout starts
        blocker = tmp_path / "site-packages-parent"
        blocker.write_text("not a directory")
        monkeypatch.setattr(jaxenv, "_DEFAULT_CACHE_DIR",
                            str(blocker / ".jax_cache"))
        with pytest.raises(RuntimeError,
                           match="JAX_COMPILATION_CACHE_DIR"):
            jaxenv.enable_compilation_cache()
        assert jax.config.jax_compilation_cache_dir == fixed
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        assert jaxenv.enable_compilation_cache() == outside
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_deploy_gcloud_commands():
    from scanner_tpu.deploy import (CloudConfig, Cluster, ClusterConfig,
                                    MachineType)
    cfg = ClusterConfig(id="sc", num_workers=2,
                        worker=MachineType(tpu_type="v5litepod-8",
                                           spot=True),
                        autoscale=True)
    cluster = Cluster(CloudConfig(project="proj", zone="us-east5-a"), cfg)
    cmds = cluster.create_commands()
    assert cmds[0][:3] == ["gcloud", "container", "--project"]
    # multi-host + autoscale: one pool PER candidate slice (autoscale cap
    # = 2x num_workers), each 0..hosts nodes
    pools = cmds[1:]
    assert len(pools) == 4
    for i, pool in enumerate(pools):
        assert "node-pools" in pool and "--spot" in pool
        assert pool[pool.index("create") + 1] == f"sc-tpu-{i}"
        assert "--enable-autoscaling" in pool
        # active slices start full; surplus autoscale pools start empty
        want_nodes = "2" if i < 2 else "0"
        assert pool[pool.index("--num-nodes") + 1] == want_nodes
        assert "ct5lp-hightpu-4t" in pool
        # GKE needs the physical slice topology
        assert pool[pool.index("--tpu-topology") + 1] == "2x4"
        assert pool[pool.index("--max-nodes") + 1] == "2"
    from scanner_tpu.deploy import cluster_resize_commands
    # autoscale: pools pre-exist and follow their pods — no gcloud needed
    assert cluster_resize_commands(cluster.cloud, cfg, 3) == []
    # non-autoscale multi-host: slice-granular pool create/delete
    cfg2 = ClusterConfig(id="sc", num_workers=2,
                         worker=MachineType(tpu_type="v5litepod-8"))
    grow = cluster_resize_commands(cluster.cloud, cfg2, 3)
    assert len(grow) == 1 and "sc-tpu-2" in grow[0]
    shrink = cluster_resize_commands(cluster.cloud, cfg2, 1)
    assert len(shrink) == 1 and "delete" in shrink[0] \
        and "sc-tpu-1" in shrink[0]
    dele = cluster.delete_commands()[0]
    assert "delete" in dele and "sc" in dele
    # spot pricing discounts
    assert MachineType(tpu_type="v5litepod-8", spot=True).price_per_hour() \
        < MachineType(tpu_type="v5litepod-8").price_per_hour()
