"""CI workflow builders: Python that emits pipeline YAML.

The reference's CI pipelines are themselves Python programs that emit
Argo Workflow specs (`/root/reference/py/kubeflow/kubeflow/ci/
notebook_controller_tests.py:1-63`, shared builders in
`workflow_utils.py`; CD twins under `cd/`). Same idea here, targeting
GitHub-Actions-shaped YAML: one generator per component family, a shared
builder, and a `main()` that writes `.github/workflows/`. Pipelines stay
reviewable as code and regenerable (`python -m ci.workflows`).
"""

from __future__ import annotations

import os
from typing import Any

COMPONENTS: dict[str, dict[str, Any]] = {
    # component -> {paths that trigger it, test command}
    "compute": {
        "paths": ["kubeflow_tpu/models/**", "kubeflow_tpu/ops/**",
                  "kubeflow_tpu/parallel/**", "kubeflow_tpu/train/**"],
        "tests": ("python -m pytest tests/test_llama.py tests/test_models.py "
                  "tests/test_mesh.py tests/test_ring.py tests/test_moe.py "
                  "tests/test_pipeline.py tests/test_flash.py "
                  "tests/test_decode_attention.py "
                  "tests/test_paged_attention_kernel.py "
                  "tests/test_checkpoint.py tests/test_llama_pp.py "
                  "tests/test_lora.py tests/test_llama_moe.py "
                  "tests/test_elastic.py -q"),
    },
    "controlplane": {
        "paths": ["kubeflow_tpu/api/**", "kubeflow_tpu/controlplane/**"],
        "tests": ("python -m pytest tests/test_store.py "
                  "tests/test_notebook_controller.py tests/test_webhook.py "
                  "tests/test_culler.py tests/test_gateway.py "
                  "tests/test_profile_kfam.py tests/test_profile_plugins.py "
                  "tests/test_tensorboard.py tests/test_metrics.py "
                  "tests/test_hpo.py tests/test_modelserver.py -q"),
    },
    "web": {
        "paths": ["kubeflow_tpu/web/**", "kubeflow_tpu/cli.py"],
        "tests": "python -m pytest tests/test_web.py tests/test_cli.py -q",
    },
    "serving": {
        "paths": ["kubeflow_tpu/serving/**", "kubeflow_tpu/tenancy/**"],
        "tests": ("python -m pytest tests/test_serving.py "
                  "tests/test_speculative.py tests/test_quant.py "
                  "tests/test_continuous.py tests/test_multilora.py "
                  "tests/test_paged_kv.py tests/test_chunked_prefill.py "
                  "tests/test_spec_paged.py -q"),
    },
    "native": {
        "paths": ["native/**", "kubeflow_tpu/data/**"],
        "tests": ("make -C native && "
                  "python -m pytest tests/test_dataloader.py "
                  "tests/test_bpe.py -q"),
    },
    "tools": {
        "paths": ["tools/**"],
        "tests": "python -m pytest tests/test_memplan.py -q",
    },
    # Observability layer: unit tier plus the obs-check gate, which
    # scrapes a LIVE platform app and strict-parses the exposition —
    # render bugs fail here, not in a Prometheus dashboard later. The
    # gate's second act boots a router over stub replicas and holds the
    # federated /fleet/metrics (merged counters/histograms, zero-seeded
    # slo_burn_rate gauges) to the same contract, so the router trigger
    # paths ride along.
    "observability": {
        "paths": ["kubeflow_tpu/obs/**", "kubeflow_tpu/fleet/router.py",
                  "ci/obs_check.py"],
        "tests": ("python -m pytest tests/test_obs.py -q && "
                  "python -m ci.obs_check"),
    },
    # Fleet layer (router / registry / autoscale): pure-host code, no
    # jax at import time in the router itself, but the suite also
    # exercises the serving drain path so it runs under the CPU pin.
    "fleet": {
        "paths": ["kubeflow_tpu/fleet/**",
                  "loadtest/serving_loadtest.py"],
        "tests": "python -m pytest tests/test_fleet.py -q",
    },
    # The driver entry points (bench.py, __graft_entry__, chip_smoke)
    # run their FULL tier including the slow subprocess tests (the
    # backend-free dryrun parent): these must execute somewhere on
    # every change to those files, not just sit behind the opt-in
    # marker. What only a chip can show is chip_smoke.py's to prove.
    "driver": {
        "paths": ["bench.py", "__graft_entry__.py", "chip_smoke.py",
                  "tools/smoke_*.py", "kubeflow_tpu/compile_cache.py"],
        "tests": ("python -m pytest tests/test_driver_armor.py "
                  "tests/test_chip_contract.py "
                  "-q -m \"slow or not slow\""),
    },
}

IMAGES = ["base", "jupyter-jax", "jupyter-jax-tpu", "jupyter-jax-full",
          "jupyter-scipy", "codeserver-jax", "rstudio",
          "rstudio-tidyverse", "serving"]


def _yaml(obj: Any, indent: int = 0) -> str:
    """Minimal YAML emitter (strings, dicts, lists) — avoids a yaml dep
    ordering surprise and keeps output diff-stable."""
    pad = "  " * indent
    if isinstance(obj, dict):
        lines = []
        for k, v in obj.items():
            if isinstance(v, (dict, list)) and v:
                lines.append(f"{pad}{k}:")
                lines.append(_yaml(v, indent + 1))
            elif isinstance(v, dict):
                # Empty mapping must stay a mapping ({}), not a quoted
                # string — GHA rejects `pull_request: "{}"` as an event.
                lines.append(f"{pad}{k}: {{}}")
            elif isinstance(v, list):
                lines.append(f"{pad}{k}: []")
            elif isinstance(v, str) and "\n" in v:
                # Multi-line strings (ConfigMap payloads) as literal
                # block scalars — double-quoted flow scalars would fold
                # the newlines into spaces.
                body = "\n".join(
                    f"{pad}  {line}".rstrip() for line in v.split("\n")
                )
                marker = "|" if v.endswith("\n") else "|-"
                lines.append(f"{pad}{k}: {marker}\n{body}".rstrip("\n"))
            else:
                lines.append(f"{pad}{k}: {_scalar(v)}")
        return "\n".join(lines)
    if isinstance(obj, list):
        lines = []
        for v in obj:
            if isinstance(v, (dict, list)) and not v:
                lines.append(f"{pad}- {'{}' if isinstance(v, dict) else '[]'}")
            elif isinstance(v, dict):
                body = _yaml(v, indent + 1).lstrip()
                lines.append(f"{pad}- {body}")
            elif isinstance(v, list):
                lines.append(f"{pad}-")
                lines.append(_yaml(v, indent + 1))
            else:
                lines.append(f"{pad}- {_scalar(v)}")
        return "\n".join(lines)
    return f"{pad}{_scalar(obj)}"


def _scalar(v: Any) -> str:
    if isinstance(v, bool):
        return "true" if v else "false"
    if v is None:
        return "null"
    s = str(v)
    if isinstance(v, str):
        # Strings that YAML 1.1 would re-type must stay strings: a bare
        # python-version: 3.10 parses as the float 3.1, "on"/"off" as
        # booleans, "0x10" as 16, and an empty scalar as null (the core
        # API group "" in RBAC rules!).
        looks_typed = s == "" or s.lower() in (
            "true", "false", "null", "~", "yes", "no", "on", "off",
        )
        for parse in (float, lambda x: int(x, 0)):
            try:
                parse(s)
                looks_typed = True
                break
            except ValueError:
                pass
        if looks_typed:
            return '"' + s + '"'
    if any(c in s for c in ":{}[]#&*!|>'\"%@`") or s != s.strip():
        return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'
    return s


def unit_test_workflow(component: str) -> dict:
    """ref notebook_controller_unit_test.yaml:1-23 (checkout + make test)."""
    spec = COMPONENTS[component]
    return {
        "name": f"{component} unit tests",
        "on": {
            "pull_request": {"paths": list(spec["paths"]) + ["tests/**"]},
            "push": {"branches": ["main"]},
        },
        "jobs": {
            "test": {
                "runs-on": "ubuntu-latest",
                "steps": [
                    {"uses": "actions/checkout@v4"},
                    {"uses": "actions/setup-python@v5",
                     "with": {"python-version": "3.11"}},
                    {"run": "pip install -e .[ci] pytest"},
                    {"name": "run tests",
                     "run": spec["tests"],
                     "env": {
                         "JAX_PLATFORMS": "cpu",
                         "XLA_FLAGS":
                             "--xla_force_host_platform_device_count=8",
                     }},
                ],
            }
        },
    }


def _image_paths(image: str) -> list:
    """Trigger paths for an image. The serving image COPYs the
    framework source, so source changes must rebuild it — the other
    images are self-contained Dockerfiles."""
    paths = [f"images/{image}/**"]
    if image == "serving":
        paths += ["kubeflow_tpu/**", "pyproject.toml"]
    return paths


def image_build_workflow(image: str) -> dict:
    """ref ci/*_runner.py kaniko no-push builds: PRs build, never push."""
    return {
        "name": f"build {image} image",
        "on": {"pull_request": {"paths": _image_paths(image)}},
        "jobs": {
            "build": {
                "runs-on": "ubuntu-latest",
                "steps": [
                    {"uses": "actions/checkout@v4"},
                    {"name": "build (no push)",
                     "run": f"make -C images {image}"},
                ],
            }
        },
    }


def e2e_workflow() -> dict:
    """Out-of-process lifecycle suite (ref odh `make e2e-test` +
    run-e2e-test.sh driving e2e/notebook_*_test.go phases)."""
    return {
        "name": "platform e2e",
        "on": {"pull_request": {}, "push": {"branches": ["main"]}},
        "jobs": {
            "e2e": {
                "runs-on": "ubuntu-latest",
                "steps": [
                    {"uses": "actions/checkout@v4"},
                    {"uses": "actions/setup-python@v5",
                     "with": {"python-version": "3.11"}},
                    {"run": "pip install -e .[ci]"},
                    {"name": "real-process platform lifecycle",
                     "run": "python e2e/run_e2e.py",
                     "env": {"JAX_PLATFORMS": "cpu"}},
                ],
            }
        },
    }


def dryrun_workflow() -> dict:
    """The multichip compile gate: dryrun_multichip on a virtual mesh."""
    return {
        "name": "multichip dryrun",
        "on": {"pull_request": {}, "push": {"branches": ["main"]}},
        "jobs": {
            "dryrun": {
                "runs-on": "ubuntu-latest",
                "steps": [
                    {"uses": "actions/checkout@v4"},
                    {"uses": "actions/setup-python@v5",
                     "with": {"python-version": "3.11"}},
                    {"run": "pip install -e .[ci]"},
                    {"name": "8-device virtual mesh dryrun",
                     "run": ("python -c 'import __graft_entry__ as g; "
                             "g.dryrun_multichip(8)'"),
                     "env": {
                         "JAX_PLATFORMS": "cpu",
                         "XLA_FLAGS":
                             "--xla_force_host_platform_device_count=8",
                     }},
                ],
            }
        },
    }


def deploy_smoke_workflow() -> dict:
    """Boot-what-you-ship gate (ref nb_controller_kind_test.yaml:1-30:
    KinD + kustomize-apply + e2e): deploy/smoke.py stands the platform
    up from the COMMITTED overlay artifacts and runs the e2e suite."""
    return {
        "name": "deploy overlay smoke",
        "on": {
            "pull_request": {"paths": ["deploy/**", "e2e/**",
                                       "kubeflow_tpu/**"]},
            "push": {"branches": ["main"]},
        },
        "jobs": {
            "smoke": {
                "runs-on": "ubuntu-latest",
                "steps": [
                    {"uses": "actions/checkout@v4"},
                    {"uses": "actions/setup-python@v5",
                     "with": {"python-version": "3.11"}},
                    {"run": "pip install -e .[ci]"},
                    {"name": "boot the standalone overlay + e2e",
                     "run": "python deploy/smoke.py standalone",
                     "env": {"JAX_PLATFORMS": "cpu"}},
                ],
            }
        },
    }


def slow_tier_workflow() -> dict:
    """The compile-heavy opt-in tier: everything marked `slow` that the
    default `-m "not slow"` run (pyproject addopts) deselects. The split
    mirrors the reference's unit-vs-KinD tiering (SURVEY.md §4): fast
    feedback on every change, the expensive tier on main."""
    return {
        "name": "slow test tier",
        "on": {"push": {"branches": ["main"]}, "workflow_dispatch": {}},
        "jobs": {
            "slow": {
                "runs-on": "ubuntu-latest",
                "steps": [
                    {"uses": "actions/checkout@v4"},
                    {"uses": "actions/setup-python@v5",
                     "with": {"python-version": "3.11"}},
                    {"run": "pip install -e .[ci] pytest"},
                    {"name": "run slow-marked tests",
                     "run": "python -m pytest tests -q -m slow",
                     "env": {
                         "JAX_PLATFORMS": "cpu",
                         "XLA_FLAGS":
                             "--xla_force_host_platform_device_count=8",
                     }},
                ],
            }
        },
    }


def frontend_workflow() -> dict:
    """JS runtime tier (ref centraldashboard/karma.conf.js): the SPA's
    whole module graph is imported and DRIVEN in node+jsdom — render,
    click, assert the wire calls — not just served over HTTP."""
    return {
        "name": "frontend runtime tests",
        "on": {
            "pull_request": {"paths": ["kubeflow_tpu/web/frontend/**",
                                       "tests/frontend/**"]},
            "push": {"branches": ["main"]},
        },
        "jobs": {
            "domtest": {
                "runs-on": "ubuntu-latest",
                "steps": [
                    {"uses": "actions/checkout@v4"},
                    {"uses": "actions/setup-node@v4",
                     "with": {"node-version": "22"}},
                    {"run": "npm install jsdom@24"},
                    {"name": "drive the SPA in jsdom",
                     "run": "node tests/frontend/dom_test.mjs"},
                ],
            }
        },
    }


def serving_check_workflow() -> dict:
    """Serving correctness gate (the obs-check pattern applied to the
    paged-KV path): `make serving-check` runs BOTH test tiers of the
    serving suite on CPU, so the dense-oracle token-parity tests for
    the paged cache / radix prefix reuse (slow-marked — compile-heavy)
    execute on every serving or attention change, not just on main."""
    return {
        "name": "serving check",
        "on": {
            "pull_request": {"paths": ["kubeflow_tpu/serving/**",
                                       "kubeflow_tpu/ops/**",
                                       "tests/test_paged_kv.py",
                                       "tests/test_continuous.py",
                                       "tests/test_chunked_prefill.py",
                                       "tests/test_spec_paged.py",
                                       "Makefile"]},
            "push": {"branches": ["main"]},
        },
        "jobs": {
            "serving-check": {
                "runs-on": "ubuntu-latest",
                "steps": [
                    {"uses": "actions/checkout@v4"},
                    {"uses": "actions/setup-python@v5",
                     "with": {"python-version": "3.11"}},
                    {"run": "pip install -e .[ci] pytest"},
                    {"name": "paged-KV dense-oracle parity gate",
                     "run": "make serving-check",
                     "env": {
                         "JAX_PLATFORMS": "cpu",
                         "XLA_FLAGS":
                             "--xla_force_host_platform_device_count=8",
                     }},
                ],
            }
        },
    }


def fleet_check_workflow() -> dict:
    """Fleet router acceptance gate: `make fleet-check` runs the unit
    suite AND a 2-replica loadtest through the router, so the
    prefix-affinity hit-rate claim and the drain/failover behavior are
    re-proven on every fleet or serving change — not asserted once in
    a perf note and left to rot."""
    return {
        "name": "fleet check",
        "on": {
            "pull_request": {"paths": ["kubeflow_tpu/fleet/**",
                                       "kubeflow_tpu/serving/**",
                                       "loadtest/serving_loadtest.py",
                                       "tests/test_fleet.py",
                                       "tests/test_migration.py",
                                       "Makefile"]},
            "push": {"branches": ["main"]},
        },
        "jobs": {
            "fleet-check": {
                "runs-on": "ubuntu-latest",
                "steps": [
                    {"uses": "actions/checkout@v4"},
                    {"uses": "actions/setup-python@v5",
                     "with": {"python-version": "3.11"}},
                    {"run": "pip install -e .[ci] pytest"},
                    {"name": "fleet unit + routed loadtest gate",
                     "run": "make fleet-check",
                     "env": {"JAX_PLATFORMS": "cpu"}},
                ],
            }
        },
    }


def chaos_check_workflow() -> dict:
    """Fault-injection gate: `make chaos-check` runs the migration
    token-identity/rollback suite AND the seeded chaos loadtest —
    drop/delay/duplicate faults, a SIGKILLed replica, an instant
    migrate-drain, and a wedged-transfer probe, all asserted to zero
    client-visible failures and token-exact streams. Failover and
    drain are robustness claims; this keeps them re-proven on every
    serving or fleet change instead of measured once and left to
    rot."""
    return {
        "name": "chaos check",
        "on": {
            "pull_request": {"paths": ["kubeflow_tpu/fleet/**",
                                       "kubeflow_tpu/serving/**",
                                       "loadtest/serving_loadtest.py",
                                       "tests/test_fleet.py",
                                       "tests/test_migration.py",
                                       "Makefile"]},
            "push": {"branches": ["main"]},
        },
        "jobs": {
            "chaos-check": {
                "runs-on": "ubuntu-latest",
                "steps": [
                    {"uses": "actions/checkout@v4"},
                    {"uses": "actions/setup-python@v5",
                     "with": {"python-version": "3.11"}},
                    {"run": "pip install -e .[ci] pytest"},
                    {"name": "migration suite + chaos loadtest gate",
                     "run": "make chaos-check",
                     "env": {"JAX_PLATFORMS": "cpu"}},
                ],
            }
        },
    }


def train_check_workflow() -> dict:
    """Elastic-training gate: `make train-check` runs the resize/ZeRO/
    commit-marker suites, the train_* metric zero-seed check, and the
    trainer chaos loadtest — a SIGKILL mid-step and another mid-
    checkpoint-save, each gang required to auto-resume at N-1 replicas
    from the last COMMITTED checkpoint with a loss curve matching the
    fault-free oracle. Elasticity is a robustness claim; this keeps it
    re-proven on every train/parallel/fleet change."""
    return {
        "name": "train check",
        "on": {
            "pull_request": {"paths": ["kubeflow_tpu/train/**",
                                       "kubeflow_tpu/parallel/**",
                                       "kubeflow_tpu/fleet/registry.py",
                                       "loadtest/serving_loadtest.py",
                                       "tests/test_elastic.py",
                                       "tests/test_checkpoint.py",
                                       "ci/obs_check.py",
                                       "Makefile"]},
            "push": {"branches": ["main"]},
        },
        "jobs": {
            "train-check": {
                "runs-on": "ubuntu-latest",
                "steps": [
                    {"uses": "actions/checkout@v4"},
                    {"uses": "actions/setup-python@v5",
                     "with": {"python-version": "3.11"}},
                    {"run": "pip install -e .[ci] pytest"},
                    {"name": "elastic suites + trainer chaos gate",
                     "run": "make train-check",
                     "env": {"JAX_PLATFORMS": "cpu"}},
                ],
            }
        },
    }


def train_obs_check_workflow() -> dict:
    """Training-observatory gate (ISSUE 14): `make train-obs-check`
    runs the goodput-ledger suite (conservation on scripted clocks,
    replay attribution across a kill/restore, straggler-ratio math,
    the heartbeat -> /elastic/metrics federation round-trip, train SLO
    burn windows, trace-merge track naming) plus the federated metrics
    contract: the goodput catalog zero-seeded in one coordinator
    scrape and the conservation EQUALITY — summed per-cause counters
    == summed wall gauges == the workers' own ledgers — held across
    the federation boundary. Any new wait the trainer grows that
    forgets to book its cause fails here, not in a capacity review."""
    return {
        "name": "train obs check",
        "on": {
            "pull_request": {"paths": ["kubeflow_tpu/train/**",
                                       "kubeflow_tpu/obs/**",
                                       "tests/test_train_obs.py",
                                       "ci/obs_check.py",
                                       "Makefile"]},
            "push": {"branches": ["main"]},
        },
        "jobs": {
            "train-obs-check": {
                "runs-on": "ubuntu-latest",
                "steps": [
                    {"uses": "actions/checkout@v4"},
                    {"uses": "actions/setup-python@v5",
                     "with": {"python-version": "3.11"}},
                    {"run": "pip install -e .[ci] pytest"},
                    {"name": "goodput ledger suite + federated "
                             "conservation contract",
                     "run": "make train-obs-check",
                     "env": {"JAX_PLATFORMS": "cpu"}},
                ],
            }
        },
    }


def disagg_check_workflow() -> dict:
    """Disaggregated-serving gate (ISSUE 12): `make disagg-check` runs
    the pool/handoff unit suite (pool-aware pick, handoff token parity
    vs the symmetric oracle on two model families, dead-prefill retry,
    autoscaler pool-split math), the pool-labeled metrics contract
    (`fleet_replicas{state,pool}` / `fleet_route_total{reason,pool}` /
    `fleet_handoff_*` zero-seeded and moved by a real handoff), and
    the equal-capacity disagg-vs-symmetric A/B loadtest with a
    SIGKILLed prefill replica. Disaggregation is both a perf claim and
    a robustness claim; this re-proves both on every fleet or serving
    change."""
    return {
        "name": "disagg check",
        "on": {
            "pull_request": {"paths": ["kubeflow_tpu/fleet/**",
                                       "kubeflow_tpu/serving/**",
                                       "loadtest/serving_loadtest.py",
                                       "tests/test_disagg.py",
                                       "tests/test_fleet.py",
                                       "ci/obs_check.py",
                                       "Makefile"]},
            "push": {"branches": ["main"]},
        },
        "jobs": {
            "disagg-check": {
                "runs-on": "ubuntu-latest",
                "steps": [
                    {"uses": "actions/checkout@v4"},
                    {"uses": "actions/setup-python@v5",
                     "with": {"python-version": "3.11"}},
                    {"run": "pip install -e .[ci] pytest"},
                    {"name": "pool suite + metrics contract + "
                             "disagg A/B gate",
                     "run": "make disagg-check",
                     "env": {"JAX_PLATFORMS": "cpu"}},
                ],
            }
        },
    }


def cache_check_workflow() -> dict:
    """KV-cache observatory gate (ISSUE 13): `make cache-check` runs
    the block-lifecycle ledger suite (conservation under radix reuse /
    preemption / migration / duplicate import, reuse-distance math on
    a scripted trace, decayed heat ranking, heartbeat digest
    round-trip, the router's two-real-replica counterfactual counter)
    plus the cache metrics contract (`serving_kv_evictions_total`
    cause set zero-seeded with cause sums == ledger frees and zero
    `unattributed`, defer causes, tenant-labelled hit/miss series,
    hashed heat digest on `/v1/models`). The conservation invariant is
    structural — any new `pool.free()` site that forgets its cause
    fails here, not in a dashboard six weeks later."""
    return {
        "name": "cache check",
        "on": {
            "pull_request": {"paths": ["kubeflow_tpu/obs/**",
                                       "kubeflow_tpu/serving/**",
                                       "kubeflow_tpu/fleet/**",
                                       "tests/test_cachestats.py",
                                       "ci/obs_check.py",
                                       "Makefile"]},
            "push": {"branches": ["main"]},
        },
        "jobs": {
            "cache-check": {
                "runs-on": "ubuntu-latest",
                "steps": [
                    {"uses": "actions/checkout@v4"},
                    {"uses": "actions/setup-python@v5",
                     "with": {"python-version": "3.11"}},
                    {"run": "pip install -e .[ci] pytest"},
                    {"name": "ledger suite + cache metrics contract",
                     "run": "make cache-check",
                     "env": {"JAX_PLATFORMS": "cpu"}},
                ],
            }
        },
    }


def cache_tier_check_workflow() -> dict:
    """Fleet cache-tier gate (ISSUE 19): `make cache-tier-check` runs
    the spill-tier suite (spill/restore token parity on two model
    families, the EXTENDED conservation invariant births − frees ==
    live + spilled, budget-ordered host evictions, the peer-fetch
    degradation matrix — dead peer / geometry mismatch / stale hint
    all fall back to plain prefill token-identically — and the
    router's X-KV-Peer hint through two real replicas) plus the tier
    metrics contract (`serving_prefill_tokens{source}` and
    `fleet_peer_fetch_total{outcome}` zero-seeded over their CLOSED
    sets, spill counters == ledger books, a live demote->restore
    round-trip replaying token-identically under pressure)."""
    return {
        "name": "cache tier check",
        "on": {
            "pull_request": {"paths": ["kubeflow_tpu/obs/**",
                                       "kubeflow_tpu/serving/**",
                                       "kubeflow_tpu/fleet/**",
                                       "tests/test_cache_tier.py",
                                       "ci/obs_check.py",
                                       "Makefile"]},
            "push": {"branches": ["main"]},
        },
        "jobs": {
            "cache-tier-check": {
                "runs-on": "ubuntu-latest",
                "steps": [
                    {"uses": "actions/checkout@v4"},
                    {"uses": "actions/setup-python@v5",
                     "with": {"python-version": "3.11"}},
                    {"run": "pip install -e .[ci] pytest"},
                    {"name": "spill/peer suite + tier metrics contract",
                     "run": "make cache-tier-check",
                     "env": {"JAX_PLATFORMS": "cpu"}},
                ],
            }
        },
    }


def control_check_workflow() -> dict:
    """Closed-loop control gate (ISSUE 16): `make control-check` runs
    the controller suite (hysteresis/cooldown math on a fake clock,
    decision-ledger conservation, every actuator through a stub
    router, verdict booking after the recovery window, the
    /fleet/decisions round-trip) plus the decision-plane metrics
    contract (policy x outcome and policy x action grids zero-seeded,
    ledger conserved over a live router, the fired action auditable
    with its control.action span). The conservation invariant is
    structural — a controller path that forgets to book its outcome
    fails here, not during the next incident."""
    return {
        "name": "control check",
        "on": {
            "pull_request": {"paths": ["kubeflow_tpu/fleet/**",
                                       "kubeflow_tpu/obs/**",
                                       "kubeflow_tpu/serving/**",
                                       "kubeflow_tpu/train/elastic.py",
                                       "loadtest/serving_loadtest.py",
                                       "tests/test_control.py",
                                       "ci/obs_check.py",
                                       "Makefile"]},
            "push": {"branches": ["main"]},
        },
        "jobs": {
            "control-check": {
                "runs-on": "ubuntu-latest",
                "steps": [
                    {"uses": "actions/checkout@v4"},
                    {"uses": "actions/setup-python@v5",
                     "with": {"python-version": "3.11"}},
                    {"run": "pip install -e .[ci] pytest"},
                    {"name": "controller suite + decision-plane "
                             "metrics contract",
                     "run": "make control-check",
                     "env": {"JAX_PLATFORMS": "cpu"}},
                ],
            }
        },
    }


def rollout_check_workflow() -> dict:
    """Live-deployment gate (ISSUE 18): `make rollout-check` runs the
    rollout suite (version-registry round-trip, ledger conservation,
    canary promote/rollback state machines on a fake clock, the
    /v1/reload drain-then-swap token parity on a live replica, the
    chief's publish hook), the rollout-plane metrics contract
    (fleet_rollout_* grids zero-seeded, /fleet/rollouts conserved
    across a promote and an SLO-burn rollback), and the mid-flood
    loadtest: a 4-replica fleet rolls a weight update under
    continuous traffic with zero client failures and byte-exact
    tokens, then a deliberately-bad version auto-rolls-back on
    canary SLO burn."""
    return {
        "name": "rollout check",
        "on": {
            "pull_request": {"paths": ["kubeflow_tpu/fleet/**",
                                       "kubeflow_tpu/obs/**",
                                       "kubeflow_tpu/serving/**",
                                       "kubeflow_tpu/train/elastic.py",
                                       "kubeflow_tpu/train/checkpoint.py",
                                       "loadtest/serving_loadtest.py",
                                       "tests/test_rollout.py",
                                       "ci/obs_check.py",
                                       "Makefile"]},
            "push": {"branches": ["main"]},
        },
        "jobs": {
            "rollout-check": {
                "runs-on": "ubuntu-latest",
                "steps": [
                    {"uses": "actions/checkout@v4"},
                    {"uses": "actions/setup-python@v5",
                     "with": {"python-version": "3.11"}},
                    {"run": "pip install -e .[ci] pytest"},
                    {"name": "rollout suite + metrics contract + "
                             "mid-flood roll/rollback loadtest",
                     "run": "make rollout-check",
                     "env": {"JAX_PLATFORMS": "cpu"}},
                ],
            }
        },
    }


def scenario_check_workflow() -> dict:
    """Scenario-engine gate (ISSUE 20): `make scenario-check` runs the
    trace/generator/replay suite (canonical byte-identity, seeded
    determinism, shape properties, fake-clock arrival fidelity, live
    abandon cancellation), the record->replay contract against a stub
    replica (ci.obs_check scenario), two pathological generated
    scenarios — a flash crowd and an abandon-retry storm — replayed
    against the full router+fleet stack with their expect SLO blocks
    asserted, and the fidelity gate: a tenant-flood run recorded off
    the live timeline store and replayed paired-interleaved with the
    original, p95 TTFT required within 10%. Traffic shapes are
    artifacts here; this keeps every committed one replayable and
    every recorded one faithful."""
    return {
        "name": "scenario check",
        "on": {
            "pull_request": {"paths": ["kubeflow_tpu/scenarios/**",
                                       "kubeflow_tpu/obs/**",
                                       "kubeflow_tpu/serving/**",
                                       "kubeflow_tpu/fleet/**",
                                       "loadtest/serving_loadtest.py",
                                       "loadtest/scenarios/**",
                                       "tests/test_scenarios.py",
                                       "ci/obs_check.py",
                                       "Makefile"]},
            "push": {"branches": ["main"]},
        },
        "jobs": {
            "scenario-check": {
                "runs-on": "ubuntu-latest",
                "steps": [
                    {"uses": "actions/checkout@v4"},
                    {"uses": "actions/setup-python@v5",
                     "with": {"python-version": "3.11"}},
                    {"run": "pip install -e .[ci] pytest"},
                    {"name": "trace suite + record/replay contract + "
                             "fleet scenarios + fidelity gate",
                     "run": "make scenario-check",
                     "env": {"JAX_PLATFORMS": "cpu"}},
                ],
            }
        },
    }


def tenancy_check_workflow() -> dict:
    """Multi-tenant QoS gate: `make tenancy-check` runs the tenancy
    unit suite (fair-share math, preemption token-identity, prefix
    isolation, header plumbing) AND the noisy-neighbor A/B loadtest,
    so the interactive-TTFT-under-batch-flood claim is re-proven on
    every scheduler or serving change — not measured once in a perf
    note and left to rot."""
    return {
        "name": "tenancy check",
        "on": {
            "pull_request": {"paths": ["kubeflow_tpu/tenancy/**",
                                       "kubeflow_tpu/serving/**",
                                       "kubeflow_tpu/fleet/**",
                                       "loadtest/serving_loadtest.py",
                                       "tests/test_tenancy.py",
                                       "Makefile"]},
            "push": {"branches": ["main"]},
        },
        "jobs": {
            "tenancy-check": {
                "runs-on": "ubuntu-latest",
                "steps": [
                    {"uses": "actions/checkout@v4"},
                    {"uses": "actions/setup-python@v5",
                     "with": {"python-version": "3.11"}},
                    {"run": "pip install -e .[ci] pytest"},
                    {"name": "QoS unit + noisy-neighbor A/B gate",
                     "run": "make tenancy-check",
                     "env": {"JAX_PLATFORMS": "cpu"}},
                ],
            }
        },
    }


def kernels_check_workflow() -> dict:
    """Pallas kernel gate: `make kernels-check` runs all three kernel
    suites (flash, fused decode, fused paged decode) in interpret mode
    on CPU, BOTH tiers — so the oracle-parity pins (including the
    slow-marked engine token-parity tests) execute on every kernel or
    attention change, not just on main's slow tier."""
    return {
        "name": "kernels check",
        "on": {
            "pull_request": {"paths": [
                "kubeflow_tpu/ops/**",
                "tests/test_flash.py",
                "tests/test_decode_attention.py",
                "tests/test_paged_attention_kernel.py",
                "tests/test_prefill_append_kernel.py",
                "Makefile"]},
            "push": {"branches": ["main"]},
        },
        "jobs": {
            "kernels-check": {
                "runs-on": "ubuntu-latest",
                "steps": [
                    {"uses": "actions/checkout@v4"},
                    {"uses": "actions/setup-python@v5",
                     "with": {"python-version": "3.11"}},
                    {"run": "pip install -e .[ci] pytest"},
                    {"name": "pallas kernels vs XLA oracles "
                             "(interpret mode)",
                     "run": "make kernels-check",
                     "env": {
                         "JAX_PLATFORMS": "cpu",
                         "XLA_FLAGS":
                             "--xla_force_host_platform_device_count=8",
                     }},
                ],
            }
        },
    }


def profile_check_workflow() -> dict:
    """Step-anatomy gate (ISSUE 8): `make profile-check` boots the
    serving app with a tiny continuous engine, drives a real generate,
    and holds `/debug/profile`, the zero-seeded phase/goodput/recompile
    metric families, and the counter-track-merged `/debug/traces` to
    the strict exposition contract."""
    return {
        "name": "profile check",
        "on": {
            "pull_request": {"paths": [
                "kubeflow_tpu/obs/**",
                "kubeflow_tpu/serving/**",
                "kubeflow_tpu/train/trainer.py",
                "kubeflow_tpu/utils/profiling.py",
                "ci/obs_check.py",
                "tests/test_profiling.py",
                "Makefile"]},
            "push": {"branches": ["main"]},
        },
        "jobs": {
            "profile-check": {
                "runs-on": "ubuntu-latest",
                "steps": [
                    {"uses": "actions/checkout@v4"},
                    {"uses": "actions/setup-python@v5",
                     "with": {"python-version": "3.11"}},
                    {"run": "pip install -e .[ci] pytest"},
                    {"name": "step-anatomy unit suite",
                     "run": ("python -m pytest tests/test_profiling.py "
                             "-q"),
                     "env": {"JAX_PLATFORMS": "cpu"}},
                    {"name": "/debug/profile + zero-seeded families "
                             "contract",
                     "run": "make profile-check"},
                ],
            }
        },
    }


def all_workflows() -> dict[str, dict]:
    from ci import cd

    out = {}
    for comp in COMPONENTS:
        out[f"{comp}_unit_test.yaml"] = unit_test_workflow(comp)
    for img in IMAGES:
        out[f"{img}_image_build.yaml"] = image_build_workflow(img)
    out["multichip_dryrun.yaml"] = dryrun_workflow()
    out["platform_e2e.yaml"] = e2e_workflow()
    out["deploy_smoke_test.yaml"] = deploy_smoke_workflow()
    out["slow_tier_test.yaml"] = slow_tier_workflow()
    out["serving_check.yaml"] = serving_check_workflow()
    out["fleet_check.yaml"] = fleet_check_workflow()
    out["chaos_check.yaml"] = chaos_check_workflow()
    out["train_check.yaml"] = train_check_workflow()
    out["train_obs_check.yaml"] = train_obs_check_workflow()
    out["disagg_check.yaml"] = disagg_check_workflow()
    out["cache_check.yaml"] = cache_check_workflow()
    out["cache_tier_check.yaml"] = cache_tier_check_workflow()
    out["control_check.yaml"] = control_check_workflow()
    out["rollout_check.yaml"] = rollout_check_workflow()
    out["scenario_check.yaml"] = scenario_check_workflow()
    out["tenancy_check.yaml"] = tenancy_check_workflow()
    out["kernels_check.yaml"] = kernels_check_workflow()
    out["profile_check.yaml"] = profile_check_workflow()
    out["frontend_test.yaml"] = frontend_workflow()
    out.update(cd.all_workflows())
    return out


def emit(outdir: str = ".github/workflows") -> list[str]:
    os.makedirs(outdir, exist_ok=True)
    written = []
    for fname, wf in sorted(all_workflows().items()):
        path = os.path.join(outdir, fname)
        with open(path, "w") as f:
            f.write("# GENERATED by ci/workflows.py — edit there, "
                    "rerun `python -m ci.workflows`.\n")
            f.write(_yaml(wf))
            f.write("\n")
        written.append(path)
    return written


if __name__ == "__main__":
    for p in emit():
        print(p)
