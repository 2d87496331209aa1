"""Cluster substrate: typed object store + storage provider + job runner.

The reference is a Kubernetes operator; its substrate (API server, CSI
driver, kubelet) is external. The TPU framework is standalone, so this
package provides the equivalent substrate natively:

- ``objects``   — the resource kinds the movers build (Volume, VolumeSnapshot,
                  Job, Service, Secret, ServiceAccount, Deployment, Event),
                  mirroring what the reference's movers create via
                  controller-runtime (SURVEY.md §2 #10-13).
- ``cluster``   — an in-process API server: CRUD with resource versions,
                  labels/owner refs, label-selector deletes, and watch
                  notification. Controller tests run against it exactly the
                  way the reference's envtest suites run against a real
                  kube-apiserver with no kubelet (SURVEY.md §4 tier 2).
- ``storage``   — directory-backed volume provisioner with snapshot/clone
                  (hardlink PiT images), the CSI analogue.
- ``runner``    — the kubelet analogue: executes Job/Deployment payloads
                  from a registered entrypoint catalog in worker threads.
                  Optional — envtest-style tests flip Job status manually.
"""

from volsync_tpu.cluster.objects import (
    Volume,
    VolumeSpec,
    VolumeStatus,
    VolumeSnapshot,
    VolumeSnapshotSpec,
    VolumeSnapshotStatus,
    Job,
    JobSpec,
    JobStatus,
    Service,
    ServicePort,
    ServiceSpec,
    ServiceStatus,
    Secret,
    ServiceAccount,
    Deployment,
    DeploymentSpec,
    DeploymentStatus,
    Event,
)
from volsync_tpu.cluster.cluster import Cluster, NotFound, Conflict
from volsync_tpu.cluster.storage import StorageProvider
from volsync_tpu.cluster.runner import JobRunner, EntrypointCatalog

__all__ = [
    "Volume",
    "VolumeSpec",
    "VolumeStatus",
    "VolumeSnapshot",
    "VolumeSnapshotSpec",
    "VolumeSnapshotStatus",
    "Job",
    "JobSpec",
    "JobStatus",
    "Service",
    "ServicePort",
    "ServiceSpec",
    "ServiceStatus",
    "Secret",
    "ServiceAccount",
    "Deployment",
    "DeploymentSpec",
    "DeploymentStatus",
    "Event",
    "Cluster",
    "NotFound",
    "Conflict",
    "StorageProvider",
    "JobRunner",
    "EntrypointCatalog",
]
