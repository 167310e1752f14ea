"""Platform CRDs: Notebook, Profile, TpuPodDefault, Tensorboard.

TPU-first redesign of the reference CRDs:
- `Notebook` (ref: notebook-controller/api/v1beta1/notebook_types.go:69-75)
  gains a first-class `tpu` block (slice topology, generation) instead of
  GPU vendor annotations; the reconciler derives gang replica count from
  the topology (one pod per TPU VM host).
- `Profile` (ref: profile-controller/api/v1/profile_types.go:63-69) quota
  includes TPU chips.
- `TpuPodDefault` (ref: admission-webhook/pkg/apis/settings/v1alpha1/
  poddefault_types.go:27-78) keeps the label-selected merge semantics and
  adds `tpu_env: bool` to opt a pod into automatic TPU_WORKER_* injection.
- `Tensorboard` (ref: tensorboard-controller/api/v1alpha1/
  tensorboard_types.go:57-63) keeps logspath dispatch (pvc:// | gs://).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import ClassVar

from kubeflow_tpu.api.core import (
    PodTemplateSpec,
    Resource,
    Toleration,
    Volume,
    VolumeMount,
    EnvVar,
)


# ---------------------------------------------------------------------------
# Notebook
# ---------------------------------------------------------------------------


@dataclass
class TpuSpec:
    """TPU attachment for a workload. Empty topology = CPU-only pod."""

    topology: str = ""          # e.g. "v5e-16" (kubeflow_tpu.parallel.mesh)
    # Parallelism layout hint injected as KFTPU_MESH for in-pod JAX.
    mesh: str = ""              # e.g. "data=1,fsdp=16,tensor=1"
    # Multi-slice job: N whole slices of `topology` gang-scheduled
    # together; the webhook injects MEGASCALE_* env so JAX builds the
    # hybrid (dcn x ici) mesh and DP rides DCN across slices.
    num_slices: int = 1
    reserved: bool = False      # use reserved capacity


@dataclass
class NotebookSpec:
    template: PodTemplateSpec = field(default_factory=PodTemplateSpec)
    tpu: TpuSpec = field(default_factory=TpuSpec)


@dataclass
class NotebookCondition:
    type: str = ""
    reason: str = ""
    message: str = ""
    last_probe_time: float = 0.0


@dataclass
class NotebookStatus:
    ready_replicas: int = 0
    container_state: str = ""   # waiting | running | terminated
    conditions: list[NotebookCondition] = field(default_factory=list)


@dataclass
class Notebook(Resource):
    KIND: ClassVar[str] = "Notebook"
    spec: NotebookSpec = field(default_factory=NotebookSpec)
    status: NotebookStatus = field(default_factory=NotebookStatus)


# Annotations shared with the reference's semantics (culler / stop):
STOP_ANNOTATION = "kubeflow-tpu.dev/stopped"           # ref culler.go:36-40
LAST_ACTIVITY_ANNOTATION = "kubeflow-tpu.dev/last-activity"
CULLING_DISABLED_ANNOTATION = "kubeflow-tpu.dev/culling-disabled"
# Webhook bookkeeping (ref admission-webhook/main.go:424-426 stamps
# poddefault.admission.kubeflow.org/poddefault-<name>=<rv>):
PODDEFAULT_APPLIED_PREFIX = "tpupoddefault.kubeflow-tpu.dev/"
WEBHOOK_EXCLUDE_ANNOTATION = "kubeflow-tpu.dev/webhook-exclude"


# ---------------------------------------------------------------------------
# Profile (multi-tenancy)
# ---------------------------------------------------------------------------


@dataclass
class ProfilePluginSpec:
    """Per-profile cloud-identity plugin (ref GetPluginSpec,
    profile_controller.go:643-675: plugins are part of the Profile CR)."""

    kind: str = ""                        # "WorkloadIdentity" | "IamForServiceAccount"
    options: dict[str, str] = field(default_factory=dict)


@dataclass
class ProfileSpec:
    owner: str = ""                       # user id (email)
    resource_quota: dict[str, str] = field(default_factory=dict)
    # e.g. {"cpu": "32", "memory": "128Gi", "tpu/v5e-chips": "16"}
    plugins: list[ProfilePluginSpec] = field(default_factory=list)


@dataclass
class ProfileStatus:
    phase: str = ""  # "" | Ready | Failed
    message: str = ""


@dataclass
class Profile(Resource):
    KIND: ClassVar[str] = "Profile"
    NAMESPACED: ClassVar[bool] = False    # cluster-scoped, owns a namespace
    spec: ProfileSpec = field(default_factory=ProfileSpec)
    status: ProfileStatus = field(default_factory=ProfileStatus)


PROFILE_FINALIZER = "profile.kubeflow-tpu.dev/cleanup"


# ---------------------------------------------------------------------------
# TpuPodDefault (PodDefault, TPU-first)
# ---------------------------------------------------------------------------


@dataclass
class TpuPodDefaultSpec:
    # label selector choosing which pods this applies to
    selector: dict[str, str] = field(default_factory=dict)
    desc: str = ""
    env: list[EnvVar] = field(default_factory=list)
    volumes: list[Volume] = field(default_factory=list)
    volume_mounts: list[VolumeMount] = field(default_factory=list)
    tolerations: list[Toleration] = field(default_factory=list)
    annotations: dict[str, str] = field(default_factory=dict)
    labels: dict[str, str] = field(default_factory=dict)
    service_account: str = ""
    command: list[str] = field(default_factory=list)
    args: list[str] = field(default_factory=list)
    # TPU-native: inject TPU_WORKER_ID/TPU_WORKER_HOSTNAMES/coordinator
    # env derived from the pod's gang position (the NCCL-free bootstrap).
    tpu_env: bool = False


@dataclass
class TpuPodDefault(Resource):
    KIND: ClassVar[str] = "TpuPodDefault"
    spec: TpuPodDefaultSpec = field(default_factory=TpuPodDefaultSpec)


# ---------------------------------------------------------------------------
# Tensorboard
# ---------------------------------------------------------------------------


@dataclass
class TensorboardSpec:
    logspath: str = ""   # "pvc://name/subpath" | "gs://bucket/path"


@dataclass
class TensorboardStatus:
    ready: bool = False
    conditions: list[dict] = field(default_factory=list)


@dataclass
class Tensorboard(Resource):
    KIND: ClassVar[str] = "Tensorboard"
    spec: TensorboardSpec = field(default_factory=TensorboardSpec)
    status: TensorboardStatus = field(default_factory=TensorboardStatus)


@dataclass
class ModelServerSpec:
    """Serve a model over REST on a TPU slice (the KServe-shaped gap:
    the reference's serving story was the removed TF-Serving component
    fronted by Service/VirtualService; here the pod runs
    `python -m kubeflow_tpu.serving`)."""

    model: str = "llama-tiny"    # serving.__main__ registry name
    # "pvc://name/subpath" (train.Checkpointer dir on a PVC),
    # "gs://bucket/path", or "" = random init (smoke/dev)
    checkpoint: str = ""
    # Fleet sizing (ISSUE 3): `replicas` is the baseline (and the
    # autoscale floor); `max_replicas > 0` enables annotation-driven
    # autoscaling — the fleet router's recommendation is written to
    # the kubeflow-tpu.dev/desired-replicas annotation and the
    # controller clamps it into [replicas, max_replicas], draining
    # excess pods before deleting them on scale-down.
    replicas: int = 1
    max_replicas: int = 0        # 0 = autoscale off
    # Disaggregated serving (ISSUE 12): when both are > 0 the fleet
    # splits into a prefill pool and a decode pool of these sizes
    # (replacing the symmetric `replicas` count; requires
    # `continuous`). Prefill pods run with zero decode pressure, fill
    # paged KV blocks, and ship them to the decode pool through the
    # router's handoff; the pools scale independently off the
    # phase-seconds split (`/fleet/autoscale?pools=1`).
    prefill_replicas: int = 0    # 0 = symmetric (no disaggregation)
    decode_replicas: int = 0
    max_len: int = 1024
    continuous: bool = True
    warmup: bool = True
    max_batch: int = 8
    prefill_chunk: int = 0       # prompt tokens a prefill slice; 0 = 256
    quant: str = ""              # "" | int8
    # "auto" = tokenizer.json beside the checkpoint when present (the
    # tools/prepare_data.py output), "none" = byte fallback forced,
    # else an explicit tokenizer file path/URL for text mode
    tokenizer: str = "auto"
    # Rollout plane (ISSUE 18): the model version label the pods BOOT
    # with ("" = unversioned). Live rollouts do not go through the
    # CRD — the RolloutManager reloads running replicas in place — but
    # the kubeflow-tpu.dev/model-version annotation (which overrides
    # this field) lets whatever consumes /fleet/versions pin the
    # version new/restarted pods come up on, so a pod restart during a
    # completed rollout does not resurrect the old weights' label.
    model_version: str = ""
    tpu: TpuSpec = field(default_factory=TpuSpec)


@dataclass
class ModelServerStatus:
    ready: bool = False
    url: str = ""
    conditions: list[dict] = field(default_factory=list)


@dataclass
class ModelServer(Resource):
    KIND: ClassVar[str] = "ModelServer"
    spec: ModelServerSpec = field(default_factory=ModelServerSpec)
    status: ModelServerStatus = field(default_factory=ModelServerStatus)


# ---------------------------------------------------------------------------
# HPO: Experiment / Trial (Katib StudyJob equivalent — the reference only
# smoke-tests Katib from outside, testing/katib_studyjob_test.py; the CRD
# itself lives in the separate katib repo, so this is a green-field design)
# ---------------------------------------------------------------------------


@dataclass
class ParameterSpec:
    """One search dimension. type: double | int | categorical."""

    name: str = ""
    type: str = "double"
    min: float = 0.0
    max: float = 0.0
    log: bool = False                      # double only
    values: list[str] = field(default_factory=list)  # categorical only


@dataclass
class ObjectiveSpec:
    metric: str = "loss"
    goal: str = "minimize"                 # minimize | maximize


@dataclass
class EarlyStoppingSpec:
    """Katib-style early stopping. `medianstop`: a running trial whose
    best objective by reported step s is worse than the MEDIAN of the
    completed trials' best-by-s is stopped (its compute freed for the
    next suggestion). Arms only once `min_trials` completed trials
    have reported intermediate metrics, and never before a trial's
    `start_step`-th report."""

    algorithm: str = ""                    # "" (off) | medianstop
    min_trials: int = 3
    start_step: int = 1


@dataclass
class ExperimentSpec:
    objective: ObjectiveSpec = field(default_factory=ObjectiveSpec)
    algorithm: str = "random"              # random | grid
    seed: int = 0
    parameters: list[ParameterSpec] = field(default_factory=list)
    max_trials: int = 10
    parallel_trials: int = 2
    early_stopping: EarlyStoppingSpec = field(
        default_factory=EarlyStoppingSpec)
    # Pod template for each trial; hyperparameters are injected as
    # KFTPU_HP_<NAME> env vars and TPU env rides the normal webhook path.
    trial_template: PodTemplateSpec = field(default_factory=PodTemplateSpec)
    tpu: TpuSpec = field(default_factory=TpuSpec)


@dataclass
class ExperimentStatus:
    phase: str = ""       # "" | Running | Succeeded | Failed
    trials_created: int = 0
    trials_succeeded: int = 0
    trials_failed: int = 0
    trials_early_stopped: int = 0
    best_trial: str = ""
    best_value: float | None = None
    best_assignment: dict[str, str] = field(default_factory=dict)
    message: str = ""


@dataclass
class Experiment(Resource):
    KIND: ClassVar[str] = "Experiment"
    spec: ExperimentSpec = field(default_factory=ExperimentSpec)
    status: ExperimentStatus = field(default_factory=ExperimentStatus)


@dataclass
class TrialSpec:
    experiment: str = ""
    assignment: dict[str, str] = field(default_factory=dict)
    template: PodTemplateSpec = field(default_factory=PodTemplateSpec)
    tpu: TpuSpec = field(default_factory=TpuSpec)
    objective_metric: str = "loss"


@dataclass
class TrialStatus:
    phase: str = ""       # "" | Running | Succeeded | Failed | EarlyStopped
    value: float | None = None
    message: str = ""
    # [step, value] pairs mirrored from the pod's intermediate-metrics
    # annotation; the median stopping rule reads these.
    intermediates: list[list[float]] = field(default_factory=list)


@dataclass
class Trial(Resource):
    KIND: ClassVar[str] = "Trial"
    spec: TrialSpec = field(default_factory=TrialSpec)
    status: TrialStatus = field(default_factory=TrialStatus)


# Trial pods report their objective via this annotation (written by the
# in-pod metric reporter; the trial controller mirrors it into status).
TRIAL_METRIC_ANNOTATION = "kubeflow-tpu.dev/metric-value"
# Progressive [step, value] JSON reported DURING the run (same writer);
# feeds the median stopping rule.
TRIAL_INTERMEDIATE_ANNOTATION = "kubeflow-tpu.dev/intermediate-metrics"
TRIAL_LABEL = "trial-name"
EXPERIMENT_LABEL = "experiment-name"
