"""Accelerator discovery for executor/feeder processes — jax-free.

Reference: ``tensorflowonspark/gpu_info.py`` (SURVEY.md §2 "GPU
allocator"): parse ``nvidia-smi``, pick free GPUs, set
``CUDA_VISIBLE_DEVICES``, retry the multi-executor grab race. On TPU
hosts the race does not exist — chips are bound to the host and owned by
whichever single process initializes the runtime — so this module only
*discovers and describes*; binding is the trainer process's act of
initializing jax (SURVEY.md §5 "Race detection"). What it does guard is
the one way that goes wrong: two claimants for one chip
(:func:`check_one_owner_per_chip`).

Must stay importable (and cheap) in processes that never touch jax.
"""

import glob
import logging
import os

logger = logging.getLogger(__name__)

MAX_RETRIES = 3  # kept for API parity with gpu_info; unused on TPU


def _accel_device_files():
    """TPU device nodes exposed by the VM image."""
    return sorted(glob.glob("/dev/accel*")) + sorted(glob.glob("/dev/vfio/*"))


def is_tpu_available():
    """True if this host exposes TPU chips (device files or env posture)."""
    if _accel_device_files():
        return True
    return bool(os.environ.get("TPU_WORKER_ID")
                or os.environ.get("TPU_SKIP_MDS_QUERY")
                or os.environ.get("JAX_PLATFORMS", "").startswith("tpu"))


# reference-name alias (gpu_info.is_gpu_available gates the same decision)
is_gpu_available = is_tpu_available


def get_devices(num_devices=None):
    """Describe local accelerator slots without initializing a runtime.

    Reference: ``gpu_info.get_gpus(num_gpus)`` returned a CSV index string
    for CUDA_VISIBLE_DEVICES. The TPU analog returns the device-file list
    (or a 1-slot placeholder when only env posture reveals the TPU); the
    trainer does NOT need it to bind — it exists for logging/diagnostics
    and for populating reservation metadata.
    """
    files = _accel_device_files()
    if not files and is_tpu_available():
        files = ["tpu:0"]
    if num_devices is not None and len(files) < num_devices:
        raise RuntimeError(
            "requested {} local TPU devices, found {}".format(
                num_devices, len(files)))
    return files


def chip_claim():
    """Which TPU chips a trainer started from this process would take,
    for the node's reservation metadata: ``None`` where it takes none
    (no TPU here, or JAX held to the CPU), the ``TPU_VISIBLE_CHIPS``
    list where the operator bound this process to some, else ``"all"``
    — initializing the runtime takes every chip of the host, and
    nothing in this framework binds an executor to a subset yet."""
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu" \
            or not is_tpu_available():
        return None
    return os.environ.get("TPU_VISIBLE_CHIPS") or "all"


def check_one_owner_per_chip(cluster_info):
    """Raise unless every TPU chip has at most one claimant among the
    cluster's nodes (``chips`` in each node's reservation metadata, see
    :func:`chip_claim`). A chip belongs to one process: two executors
    on one host whose trainers would each initialize the runtime end
    with the second one dead on libtpu's lock, deep in a child's log —
    so the cluster refuses to form instead, on the driver and on every
    node, before any trainer starts."""
    claims = {}
    for node in cluster_info:
        if node.get("chips"):
            claims.setdefault(node["host"], []).append(
                (node["executor_id"], node["chips"]))
    for host, owners in claims.items():
        taken = set()
        for _, chips in owners:
            mine = set(chips.split(","))
            if taken and ("all" in mine | taken or mine & taken):
                raise RuntimeError(
                    "executors {} share TPU host {} and would claim the "
                    "same chips ({}): a chip belongs to one process, and "
                    "a trainer takes every chip its process can see. Run "
                    "one executor per TPU host (its trainer drives all "
                    "local chips through one mesh), or bind each executor "
                    "to chips of its own with TPU_VISIBLE_CHIPS in its "
                    "environment.".format(
                        [e for e, _ in owners], host,
                        [c for _, c in owners]))
            taken |= mine


def topology_env():
    """The libtpu topology variables present in this environment, if any
    (multi-host pods publish these; useful in reservation metadata)."""
    keys = ("TPU_WORKER_ID", "TPU_WORKER_HOSTNAMES", "TPU_CHIPS_PER_HOST",
            "TPU_HOST_BOUNDS", "TPU_PROCESS_BOUNDS", "TPU_VISIBLE_CHIPS",
            "TPU_ACCELERATOR_TYPE")
    return {k: os.environ[k] for k in keys if k in os.environ}
