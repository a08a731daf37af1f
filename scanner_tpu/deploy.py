"""Cluster deployment tooling for GKE TPU pods.

Capability parity: reference scannerpy/kube.py (CloudConfig, MachineType,
ClusterConfig with price estimation, Cluster create/scale/delete managing
master + worker deployments, kube.py:38-779) — retargeted from GPU node
pools to TPU node pools, with the pieces a TPU deployment actually needs:

  * gcloud lifecycle COMMANDS are generated as pure argv lists
    (`cluster_create_commands` etc.) and only executed when gcloud is
    present — the reference shells out inline; generating first keeps
    every path unit-testable offline and lets operators audit/copy the
    exact commands.
  * workers are a StatefulSet behind a headless Service: multi-host TPU
    slices need stable pod identities so every host derives its
    jax.distributed rank from its pod ordinal and dials pod 0 as the
    coordinator (scanner_tpu/parallel/distributed.py).
  * the worker env wires SCANNER_TPU_LOG, the db path (gs:// selects the
    native GCS backend), and the coordinator address; a ConfigMap carries
    ~/.scanner_tpu.toml.
"""

from __future__ import annotations

import json
import shutil
import subprocess
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from .common import ScannerException
from .config import dump_toml

# us-central1 on-demand ballpark $/hr (documented estimates, like the
# reference's price table); spot ~= 60% off
TPU_PRICES = {
    "v5litepod-1": 1.2,
    "v5litepod-4": 4.8,
    "v5litepod-8": 9.6,
    "v5p-8": 16.6,
}
SPOT_DISCOUNT = 0.4
CPU_PRICE_PER_CORE = 0.033

# GKE node-pool accelerator labels + machine types per slice family
TPU_ACCELERATOR_LABELS = {
    "v5litepod": "tpu-v5-lite-podslice",
    "v5p": "tpu-v5p-slice",
}
TPU_MACHINE_TYPES = {
    "v5litepod": "ct5lp-hightpu-{chips}t",
    "v5p": "ct5p-hightpu-{chips}t",
}
# chips per host for multi-host topology math (v5e: 4 chips/host)
TPU_CHIPS_PER_HOST = {"v5litepod": 4, "v5p": 4}
# physical slice topologies GKE requires for TPU node pools
TPU_TOPOLOGIES = {
    "v5litepod": {1: "1x1", 4: "2x2", 8: "2x4", 16: "4x4", 32: "4x8"},
    "v5p": {8: "2x2x1", 16: "2x2x2", 32: "2x4x2"},
}
# where a worker pod keeps its XLA compile cache when the cluster config
# names no directory: a fixed path on the container's own filesystem
POD_CACHE_DIR = "/var/cache/scanner_tpu/xla"


def tpu_topology(tpu_type: str) -> str:
    family, chips = tpu_family(tpu_type), tpu_chips(tpu_type)
    try:
        return TPU_TOPOLOGIES[family][chips]
    except KeyError:
        raise ScannerException(
            f"no known GKE topology for {tpu_type}; add it to "
            f"TPU_TOPOLOGIES")


def tpu_chips(tpu_type: str) -> int:
    """Chip count from the slice name suffix ('v5litepod-4' -> 4)."""
    try:
        return int(tpu_type.rsplit("-", 1)[1])
    except (IndexError, ValueError):
        raise ScannerException(f"cannot parse TPU type: {tpu_type}")


def tpu_family(tpu_type: str) -> str:
    family = tpu_type.rsplit("-", 1)[0]
    if family not in TPU_ACCELERATOR_LABELS:
        raise ScannerException(f"unknown TPU family: {family}")
    return family


def tpu_accelerator_label(tpu_type: str) -> str:
    return TPU_ACCELERATOR_LABELS[tpu_family(tpu_type)]


def tpu_chips_per_host(tpu_type: str) -> int:
    """Chips on one host of this slice type (the pod's google.com/tpu
    limit and the gcloud machine type must agree on this)."""
    return min(tpu_chips(tpu_type), TPU_CHIPS_PER_HOST[tpu_family(tpu_type)])


def tpu_hosts(tpu_type: str) -> int:
    """Hosts in one slice (multi-host slices get one engine worker per
    host, all joined into one jax.distributed runtime)."""
    family = tpu_family(tpu_type)
    per = TPU_CHIPS_PER_HOST[family]
    chips = tpu_chips(tpu_type)
    return max(1, chips // per)


@dataclass
class CloudConfig:
    project: str
    zone: str = "us-central1-a"
    storage_bucket: Optional[str] = None


@dataclass
class MachineType:
    """One worker node shape: a TPU slice + host CPU."""

    tpu_type: str = "v5litepod-4"
    cpus: int = 24
    memory_gb: int = 96
    spot: bool = False

    def price_per_hour(self) -> float:
        price = TPU_PRICES.get(self.tpu_type, 0.0) \
            + self.cpus * CPU_PRICE_PER_CORE
        return price * SPOT_DISCOUNT if self.spot else price

    def machine_type(self) -> str:
        return TPU_MACHINE_TYPES[tpu_family(self.tpu_type)].format(
            chips=tpu_chips_per_host(self.tpu_type))


@dataclass
class ClusterConfig:
    id: str
    num_workers: int
    master_cpus: int = 8
    worker: MachineType = field(default_factory=MachineType)
    image: str = "scanner-tpu:latest"
    db_path: str = "/data/db"      # or gs://bucket/db for the GCS backend
    master_port: int = 5000
    # None = workers resolve one device-affine pipeline instance per
    # local chip (engine/evaluate.py default_pipeline_instances); an
    # explicit int — including 1 — is used as given
    pipeline_instances: Optional[int] = None
    log_level: str = "info"
    autoscale: bool = False
    max_workers: Optional[int] = None
    # 0 = no /metrics|/healthz|/statusz endpoint (the default); non-zero
    # serves it on that port on master AND workers and exposes the
    # container port for Prometheus scraping (docs/observability.md)
    metrics_port: int = 0
    # persistent XLA compilation-cache directory for workers, emitted as
    # each worker's JAX_COMPILATION_CACHE_DIR env var (JAX reads it
    # itself).  "" = POD_CACHE_DIR, pod-local scratch that dies with
    # the pod: the image's installed package has no writable checkout
    # for the in-code default (util/jaxenv.py).  Point it at a gs://
    # prefix shared by the fleet and a restarted/rescheduled worker
    # re-loads its jitted kernel executables instead of re-paying TPU
    # compile time per bucket shape (PERF.md "Bring-up on v5e").
    compilation_cache_dir: str = ""
    # seconds kubernetes waits between SIGTERM and SIGKILL on worker
    # pods.  start_worker maps SIGTERM to drain mode (finish in-flight
    # tasks, stop pulling, deregister — engine/service.py
    # Worker.drain), so size this to cover the longest task plus its
    # save; a too-small value turns every rolling update into a crash
    # the stale scan must clean up.
    termination_grace_period: int = 120
    # user alert rules appended to the built-in health/SLO ruleset
    # (docs/observability.md §Health & SLOs clause grammar); wired into
    # the ConfigMap's [alerts] section so every pod's engine evaluates
    # them.  "" = defaults only.
    alert_rules: str = ""
    # alert->action remediation (engine/controller.py), wired into the
    # ConfigMap's [remediation] section for every pod.  False =
    # signal-only (alerts fire, nothing actuates); dry_run keeps the
    # decision pipeline + audit live without invoking actions.  The
    # autoscaler bounds feed Master(autoscale=True); the production
    # actuator is Cluster.scale (scale-down drains pods via SIGTERM ->
    # Worker.drain, so in-flight tasks are never killed).
    remediation: bool = True
    remediation_dry_run: bool = False
    autoscale_min: int = 1
    autoscale_max: int = 8
    # gang-scheduled multi-host execution (engine/gang.py, docs/
    # robustness.md §Gang scheduling).  Workers advertise a gang
    # coordinator port from their pod DNS name automatically (any pod
    # port is reachable inside the cluster network — no containerPort
    # row needed); these knobs wire the [gang] ConfigMap section +
    # each worker's rendezvous bound.  Disable for fleets that never
    # run gang bulks to skip the per-worker port reservation.
    gang: bool = True
    gang_init_timeout_s: int = 60
    gang_form_timeout_s: int = 5
    # mesh-partitioned gang evaluation (members compute only their row
    # shard; ~N× per-gang throughput) and the stencil halo exchange
    # that rides on it — the fleet-wide [gang] sharded/halo_exchange
    # defaults; gang_sharded=False pins a fleet to the replicated
    # N×-redundant evaluation (the A/B + fallback mode)
    gang_sharded: bool = True
    gang_halo_exchange: bool = True

    def price_per_hour(self) -> float:
        return (self.master_cpus * CPU_PRICE_PER_CORE
                + self.num_workers * self.worker.price_per_hour())


# ---------------------------------------------------------------------------
# gcloud lifecycle commands (pure; execution is optional)
# ---------------------------------------------------------------------------

def cluster_create_commands(cloud: CloudConfig,
                            cfg: ClusterConfig) -> List[List[str]]:
    """argv lists that bring up the GKE cluster + TPU node pool
    (reference kube.py get_or_create_cluster; gcloud only runs when the
    operator executes these)."""
    base = ["gcloud", "container", "--project", cloud.project]
    hosts = tpu_hosts(cfg.worker.tpu_type)
    cmds = [
        base + ["clusters", "create", cfg.id,
                "--zone", cloud.zone,
                "--num-nodes", "1",
                "--machine-type", f"n2-standard-{cfg.master_cpus}"],
    ]

    def pool_cmd(name: str, nodes: int) -> List[str]:
        c = base + ["node-pools", "create", name,
                    "--cluster", cfg.id,
                    "--zone", cloud.zone,
                    "--machine-type", cfg.worker.machine_type(),
                    "--tpu-topology", tpu_topology(cfg.worker.tpu_type),
                    "--num-nodes", str(nodes)]
        if cfg.worker.spot:
            c.append("--spot")
        return c

    if hosts <= 1:
        pool = pool_cmd(f"{cfg.id}-tpu", cfg.num_workers)
        if cfg.autoscale:
            max_slices = cfg.max_workers or cfg.num_workers * 2
            pool += ["--enable-autoscaling", "--min-nodes", "0",
                     "--max-nodes", str(max_slices)]
        cmds.append(pool)
    else:
        # one node pool PER SLICE: a multi-host coordinator group must be
        # slice-coherent, and only a dedicated pool (selected via
        # cloud.google.com/gke-nodepool) guarantees its pods land on one
        # physical slice.  With autoscale, idle slices park at 0 nodes.
        n_pools = (cfg.max_workers or cfg.num_workers * 2) \
            if cfg.autoscale else cfg.num_workers
        for i in range(n_pools):
            # surplus autoscale pools (no StatefulSet yet) start empty:
            # the autoscaler fills a slice pool only when its pods arrive
            nodes = hosts if i < cfg.num_workers else 0
            pool = pool_cmd(f"{cfg.id}-tpu-{i}", nodes)
            if cfg.autoscale:
                pool += ["--enable-autoscaling", "--min-nodes", "0",
                         "--max-nodes", str(hosts)]
            cmds.append(pool)
    return cmds


def cluster_delete_commands(cloud: CloudConfig,
                            cfg: ClusterConfig) -> List[List[str]]:
    return [["gcloud", "container", "--project", cloud.project,
             "clusters", "delete", cfg.id, "--zone", cloud.zone,
             "--quiet"]]


def cluster_resize_commands(cloud: CloudConfig, cfg: ClusterConfig,
                            num_workers: int) -> List[List[str]]:
    """Scale worker capacity from cfg.num_workers to num_workers.
    Single-host: resize the shared pool.  Multi-host: slices scale by
    creating/deleting whole per-slice pools."""
    hosts = tpu_hosts(cfg.worker.tpu_type)
    base = ["gcloud", "container", "--project", cloud.project]
    if cfg.autoscale:
        # autoscaling pools follow their pods: scaling is kubectl-only
        # (per-slice pools were pre-created 0..hosts at cluster create,
        # and re-creating them here would fail with already-exists)
        return []
    if hosts <= 1:
        return [base + ["clusters", "resize", cfg.id,
                        "--node-pool", f"{cfg.id}-tpu",
                        "--num-nodes", str(num_workers),
                        "--zone", cloud.zone, "--quiet"]]
    cur = cfg.num_workers
    cmds = []
    for i in range(cur, num_workers):       # grow: add slice pools
        c = base + ["node-pools", "create", f"{cfg.id}-tpu-{i}",
                    "--cluster", cfg.id,
                    "--zone", cloud.zone,
                    "--machine-type", cfg.worker.machine_type(),
                    "--tpu-topology", tpu_topology(cfg.worker.tpu_type),
                    "--num-nodes", str(hosts)]
        if cfg.worker.spot:
            c.append("--spot")
        cmds.append(c)
    for i in range(num_workers, cur):       # shrink: drop slice pools
        cmds.append(base + ["node-pools", "delete", f"{cfg.id}-tpu-{i}",
                            "--cluster", cfg.id,
                            "--zone", cloud.zone, "--quiet"])
    return cmds


# ---------------------------------------------------------------------------
# kubernetes manifests (pure)
# ---------------------------------------------------------------------------

def config_manifest(cfg: ClusterConfig) -> Dict:
    """ConfigMap carrying ~/.scanner_tpu.toml for every pod."""
    sections = {
        "storage": {"type": "gcs" if cfg.db_path.startswith("gs://")
                    else "posix",
                    "db_path": cfg.db_path},
        "network": {"master": f"{cfg.id}-master",
                    "master_port": cfg.master_port,
                    "worker_port": 5001,
                    "metrics_port": cfg.metrics_port},
    }
    if cfg.alert_rules:
        sections["alerts"] = {"rules": cfg.alert_rules}
    sections["remediation"] = {
        "enabled": cfg.remediation,
        "dry_run": cfg.remediation_dry_run,
        "autoscale_min": cfg.autoscale_min,
        "autoscale_max": cfg.autoscale_max,
    }
    sections["gang"] = {
        "enabled": cfg.gang,
        "init_timeout_s": cfg.gang_init_timeout_s,
        "form_timeout_s": cfg.gang_form_timeout_s,
        "sharded": cfg.gang_sharded,
        "halo_exchange": cfg.gang_halo_exchange,
    }
    toml = dump_toml(sections)
    return {
        "apiVersion": "v1", "kind": "ConfigMap",
        "metadata": {"name": f"{cfg.id}-config"},
        "data": {"scanner_tpu.toml": toml},
    }


def _metrics_arg(cfg: ClusterConfig) -> str:
    return f", metrics_port={cfg.metrics_port}" if cfg.metrics_port else ""


def _probes(cfg: ClusterConfig) -> Dict:
    """Container liveness/readiness probes against the metrics
    endpoint's health routes (util/metrics.py MetricsServer).
    Liveness -> /healthz, which answers 200 whenever the process can
    answer at all: alert states (HBM pressure, latency burn) are
    workload facts a restart cannot fix, so the probe only fails when
    the process is dead or wedged.  Readiness -> /readyz, which goes
    503 while the health roll-up is `unhealthy` OR a SIGTERM drain is
    in progress — k8s stops routing to the pod while its in-flight
    tasks finish instead of killing it.  Only emitted when the
    endpoint exists (metrics_port set)."""
    if not cfg.metrics_port:
        return {}
    return {
        "livenessProbe": {
            "httpGet": {"path": "/healthz", "port": cfg.metrics_port},
            "periodSeconds": 10, "failureThreshold": 6},
        "readinessProbe": {
            "httpGet": {"path": "/readyz", "port": cfg.metrics_port},
            "periodSeconds": 5, "failureThreshold": 2},
    }


def master_manifest(cfg: ClusterConfig) -> Dict:
    ports = [{"containerPort": cfg.master_port}]
    if cfg.metrics_port:
        ports.append({"containerPort": cfg.metrics_port,
                      "name": "metrics"})
    return {
        "apiVersion": "apps/v1", "kind": "Deployment",
        "metadata": {"name": f"{cfg.id}-master"},
        "spec": {
            "replicas": 1,
            "selector": {"matchLabels": {"app": f"{cfg.id}-master"}},
            "template": {
                "metadata": {"labels": {"app": f"{cfg.id}-master"}},
                "spec": {"containers": [{
                    "name": "master", "image": cfg.image,
                    "command": ["python", "-c",
                                ("from scanner_tpu.engine.service import "
                                 "start_master; start_master("
                                 f"'{cfg.db_path}', port={cfg.master_port}"
                                 f"{_metrics_arg(cfg)},"
                                 " block=True)")],
                    "env": [{"name": "SCANNER_TPU_LOG",
                             "value": cfg.log_level}],
                    "ports": ports,
                    **_probes(cfg),
                    "resources": {"requests": {"cpu": str(cfg.master_cpus)}},
                }]},
            },
        },
    }


def _worker_command(cfg: ClusterConfig, hosts: int,
                    slice_idx: int = 0) -> List[str]:
    """Worker entry: single-host slices start a plain worker; multi-host
    slices derive the in-slice rank directly from the pod ordinal (each
    slice is its own StatefulSet) and join pod 0's jax.distributed
    coordinator before serving."""
    # each pod advertises its stable headless-service DNS name so the
    # master's GetMetrics aggregation can dial it cross-host (a bare
    # localhost registration would silently drop every worker from the
    # cluster metrics view)
    adv = (f"advertise_host=os.environ['POD_NAME'] + "
           f"'.{cfg.id}-workers', ")
    if hosts <= 1:
        return ["python", "-c",
                ("import os; "
                 "from scanner_tpu.engine.service import start_worker; "
                 f"start_worker('{cfg.id}-master:{cfg.master_port}', "
                 f"'{cfg.db_path}', "
                 f"pipeline_instances={cfg.pipeline_instances}"
                 f"{_metrics_arg(cfg)}, {adv}"
                 "block=True)")]
    sts = f"{cfg.id}-worker-s{slice_idx}"
    return ["python", "-c", (
        "import os; "
        "from scanner_tpu.engine.service import start_worker; "
        "from scanner_tpu.parallel.distributed import CoordinatorConfig; "
        "pid = int(os.environ['POD_NAME'].rsplit('-', 1)[1]); "
        f"coord = CoordinatorConfig("
        f"address=\"{sts}-0.{cfg.id}-workers:8476\", "
        f"num_processes={hosts}, process_id=pid); "
        f"start_worker('{cfg.id}-master:{cfg.master_port}', "
        f"'{cfg.db_path}', "
        f"pipeline_instances={cfg.pipeline_instances}"
        f"{_metrics_arg(cfg)}, {adv}"
        "coordinator=coord, block=True)")]


def _worker_statefulset(cfg: ClusterConfig, name: str, replicas: int,
                        command: List[str],
                        extra_selector: Optional[Dict] = None) -> Dict:
    per_host_chips = tpu_chips_per_host(cfg.worker.tpu_type)
    node_selector = {
        "cloud.google.com/gke-tpu-accelerator":
            tpu_accelerator_label(cfg.worker.tpu_type),
        # GKE TPU pods must state the physical slice topology they expect
        "cloud.google.com/gke-tpu-topology":
            tpu_topology(cfg.worker.tpu_type),
    }
    node_selector.update(extra_selector or {})
    return {
        "apiVersion": "apps/v1", "kind": "StatefulSet",
        "metadata": {"name": name},
        "spec": {
            "serviceName": f"{cfg.id}-workers",
            "replicas": replicas,
            "podManagementPolicy": "Parallel",
            "selector": {"matchLabels": {"app": f"{cfg.id}-worker",
                                         "sts": name}},
            "template": {
                "metadata": {"labels": {"app": f"{cfg.id}-worker",
                                        "sts": name}},
                "spec": {
                    "nodeSelector": node_selector,
                    # SIGTERM -> Worker.drain; give in-flight tasks this
                    # long to finish before the SIGKILL follow-up
                    "terminationGracePeriodSeconds":
                        cfg.termination_grace_period,
                    "containers": [{
                        "name": "worker", "image": cfg.image,
                        "command": command,
                        **({"ports": [{"containerPort": cfg.metrics_port,
                                       "name": "metrics"}]}
                           if cfg.metrics_port else {}),
                        **_probes(cfg),
                        "env": [
                            {"name": "SCANNER_TPU_LOG",
                             "value": cfg.log_level},
                            {"name": "POD_NAME",
                             "valueFrom": {"fieldRef": {
                                 "fieldPath": "metadata.name"}}},
                            # worker-side persistent XLA executable
                            # cache (JAX reads the variable itself)
                            {"name": "JAX_COMPILATION_CACHE_DIR",
                             "value": cfg.compilation_cache_dir
                             or POD_CACHE_DIR},
                            # gang member runners rendezvous with this
                            # bound (engine/gang.py); 0 also strips the
                            # gang port reservation from the worker
                            *([{"name": "SCANNER_TPU_GANG_INIT_TIMEOUT",
                                "value": str(cfg.gang_init_timeout_s)}]
                              if cfg.gang else
                              [{"name": "SCANNER_TPU_GANG",
                                "value": "0"}]),
                        ],
                        "resources": {
                            "requests": {"cpu": str(cfg.worker.cpus)},
                            "limits": {"google.com/tpu":
                                       str(per_host_chips)},
                        },
                        "volumeMounts": [{
                            "name": "config",
                            "mountPath": "/root/.scanner_tpu.toml",
                            "subPath": "scanner_tpu.toml"}],
                    }],
                    "volumes": [{"name": "config",
                                 "configMap": {
                                     "name": f"{cfg.id}-config"}}],
                },
            },
        },
    }


def worker_manifests(cfg: ClusterConfig) -> List[Dict]:
    """Worker StatefulSets behind one headless Service.

    Single-host slices: one StatefulSet, one pod per slice.  Multi-host
    slices: one StatefulSet PER SLICE, pinned to that slice's dedicated
    node pool (cloud.google.com/gke-nodepool) — nothing else guarantees a
    jax.distributed coordinator group lands on one physical slice, and a
    group split across slices hangs at initialize()."""
    hosts = tpu_hosts(cfg.worker.tpu_type)
    if hosts <= 1:
        return [_worker_statefulset(cfg, f"{cfg.id}-worker",
                                    cfg.num_workers,
                                    _worker_command(cfg, hosts))]
    return [
        _worker_statefulset(
            cfg, f"{cfg.id}-worker-s{i}", hosts,
            _worker_command(cfg, hosts, slice_idx=i),
            extra_selector={
                "cloud.google.com/gke-nodepool": f"{cfg.id}-tpu-{i}"})
        for i in range(cfg.num_workers)
    ]


def worker_manifest(cfg: ClusterConfig) -> Dict:
    """Back-compat single-manifest accessor (single-host configs)."""
    ms = worker_manifests(cfg)
    if len(ms) != 1:
        raise ScannerException(
            "multi-host configs produce one StatefulSet per slice; use "
            "worker_manifests()")
    return ms[0]


def service_manifest(cfg: ClusterConfig) -> Dict:
    return {
        "apiVersion": "v1", "kind": "Service",
        "metadata": {"name": f"{cfg.id}-master"},
        "spec": {
            "selector": {"app": f"{cfg.id}-master"},
            "ports": [{"port": cfg.master_port,
                       "targetPort": cfg.master_port}],
        },
    }


def workers_service_manifest(cfg: ClusterConfig) -> Dict:
    """Headless service giving StatefulSet pods stable DNS names
    (<pod>.<cfg.id>-workers) — the coordinator address for multi-host."""
    return {
        "apiVersion": "v1", "kind": "Service",
        "metadata": {"name": f"{cfg.id}-workers"},
        "spec": {
            "clusterIP": "None",
            "selector": {"app": f"{cfg.id}-worker"},
            "ports": [{"port": 8476, "name": "coordinator"}],
        },
    }


class Cluster:
    """Lifecycle wrapper (reference kube.py Cluster): create/scale/delete
    via gcloud/kubectl; manifests() and *_commands() work without
    either installed."""

    def __init__(self, cloud: CloudConfig, cfg: ClusterConfig):
        self.cloud = cloud
        self.cfg = cfg

    # -- pure outputs ---------------------------------------------------

    def manifests(self) -> List[Dict]:
        return [config_manifest(self.cfg), master_manifest(self.cfg),
                service_manifest(self.cfg),
                workers_service_manifest(self.cfg),
                *worker_manifests(self.cfg)]

    def manifests_json(self) -> str:
        return "\n---\n".join(json.dumps(m, indent=2)
                              for m in self.manifests())

    def create_commands(self) -> List[List[str]]:
        return cluster_create_commands(self.cloud, self.cfg)

    def delete_commands(self) -> List[List[str]]:
        return cluster_delete_commands(self.cloud, self.cfg)

    # -- execution (requires gcloud/kubectl on PATH) --------------------

    def _run(self, argv: List[str],
             input_data: Optional[str] = None):
        if shutil.which(argv[0]) is None:
            raise ScannerException(
                f"{argv[0]} not available; use manifests_json() / "
                f"*_commands() and run manually")
        return subprocess.run(argv, input=input_data, text=True,
                              check=True, capture_output=True)

    def create_cluster(self) -> None:
        for cmd in self.create_commands():
            self._run(cmd)

    def delete_cluster(self) -> None:
        for cmd in self.delete_commands():
            self._run(cmd)

    def create(self) -> None:
        self._run(["kubectl", "apply", "-f", "-"],
                  input_data=self.manifests_json())

    def scale(self, num_workers: int) -> None:
        if shutil.which("kubectl") is None:
            raise ScannerException(
                "kubectl not available; use manifests_json() / "
                "*_commands() and run manually")
        hosts = tpu_hosts(self.cfg.worker.tpu_type)
        # pool changes are derived from old-vs-new worker counts, so
        # compute them BEFORE mutating cfg
        resize = cluster_resize_commands(self.cloud, self.cfg, num_workers)
        old = self.cfg.num_workers
        if hosts <= 1:
            self._run(["kubectl", "scale",
                       f"statefulset/{self.cfg.id}-worker",
                       f"--replicas={num_workers}"])
        else:
            # slice-granular: apply manifests for the new slice set, drop
            # StatefulSets of removed slices
            self.cfg.num_workers = num_workers
            self._run(["kubectl", "apply", "-f", "-"],
                      input_data=self.manifests_json())
            for i in range(num_workers, old):
                self._run(["kubectl", "delete", "statefulset",
                           f"{self.cfg.id}-worker-s{i}", "--ignore-not-found"])
        self.cfg.num_workers = num_workers
        if not resize:
            return  # autoscaling pools follow their pods
        if shutil.which("gcloud") is None:
            # the operator applies the pool changes with the printed
            # commands
            print("deploy: gcloud not available; run manually:")
            for cmd in resize:
                print(" ", " ".join(cmd))
            return
        for cmd in resize:
            self._run(cmd)

    def delete(self) -> None:
        self._run(["kubectl", "delete", "-f", "-"],
                  input_data=self.manifests_json())

    def master_address(self) -> str:
        return f"{self.cfg.id}-master:{self.cfg.master_port}"

    def scale_actuator(self):
        """The autoscaler-facing replica setter
        (``Master(autoscale=True, scale_actuator=cluster.scale_actuator())``):
        just ``Cluster.scale`` — kubernetes removes surplus pods via
        SIGTERM, which ``start_worker`` maps to ``Worker.drain``, so an
        autoscale-down never kills in-flight tasks."""
        return self.scale
