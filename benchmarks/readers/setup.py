"""Per-layer metrics of set-up, read from the program's own compile
ledger (`kubeflow_tpu.obs.compile_ledger()`, installed by
`compile_cache.enable()`, which `run.py` calls first): what JAX traced,
lowered and compiled or read from its cache in this process, by
program, on JAX's own events; the start-up spans the engine, the
batcher and the Trainer open; and what preceded the first program.
Nothing here is timed by the benchmark.

The readers run after the window and count the programs first seen
before it opened: the ledger's installation plus the run's `setup_s`,
on the ledger's clock (nothing compiles in a window, `correct` says
so; what a close or a drain builds afterwards is not set-up's).

Each returns nothing off the chip (`peaks` is None there), where the
program has no ledger (a program older than it), where the process
never installed it, and where it holds no program. No reader raises.

The first reader called in a run also leaves the ledger's costliest
programs and the start-up spans in the line's `extra` and prints them
to stderr, so that a traced run shows which program a second went to.
"""

from __future__ import annotations

import sys

TOP = 12


def _ledger(ctx):
    if ctx.peaks is None:
        return None
    try:
        from kubeflow_tpu import obs

        ledger = obs.compile_ledger()
        if not ledger.installed or not ledger.rows():
            return None
        _show(ctx, ledger)
        return ledger
    except Exception:  # noqa: BLE001 — a reader never raises
        return None


def _window_opens(ctx, ledger) -> float | None:
    """The window's first instant on the ledger's clock."""
    setup_s = ctx.run.end_to_end.get("setup_s")
    if setup_s is None or ledger.installed_at is None:
        return None
    return ledger.installed_at + setup_s


def _show(ctx, ledger) -> None:
    if "compile_ledger" in ctx.run.extra:
        return
    snap = ledger.snapshot(top=TOP)
    cut = _window_opens(ctx, ledger)
    snap["before_window"] = ledger.totals(first_seen_before=cut)
    ctx.run.extra["compile_ledger"] = snap
    t0 = ledger.installed_at

    def say(msg: str) -> None:
        print(f"[setup] {msg}", file=sys.stderr, flush=True)

    say("before the window: " + ", ".join(
        f"{k} {v:g}" for k, v in snap["before_window"].items()))
    say(f"installed -> first program "
        f"{snap['startup']['before_first_program_s']} s")
    for r in snap["compiles"]["programs"]:
        say(f"  {r['program'][:36]:36s} +{r['first_seen'] - t0:7.2f}.."
            f"{r['last_seen'] - t0:7.2f} s  trace {r['trace_s']:7.3f} s "
            f"x{r['traces']}  lower {r['lower_s']:7.3f}  backend "
            f"{r['backend_s']:7.3f} x{r['backends']} (read "
            f"{r['cache_read_s']:.3f}, hit {r['cache_hits']} of "
            f"{r['cache_requests']} asked)")
    for name, s in snap["startup"]["spans"].items():
        say(f"  {name:36s} +{s['start'] - t0:7.2f} s  x{s['count']} "
            f"{s['seconds']:7.3f} s, under it {s['children_s']:7.3f} s")


def before_first_program_s(ctx):
    """From the ledger's installation to the start of the first
    program's first stage: the import of JAX, the device attach, and
    whatever the entry point does before it builds anything."""
    ledger = _ledger(ctx)
    return None if ledger is None else ledger.before_first_program_s()


def total(ctx, *, keys: list[str]):
    """The sum of the ledger's totals named `keys` over the programs
    first seen before the window opened."""
    ledger = _ledger(ctx)
    if ledger is None:
        return None
    totals = ledger.totals(first_seen_before=_window_opens(ctx, ledger))
    return sum(totals[k] for k in keys)


def span_self_s(ctx, *, spans: list[str]):
    """Wall time under the start-up spans named `spans`, less what
    opened under each on its thread (compile stages, other spans);
    nothing where the run opened none of them."""
    ledger = _ledger(ctx)
    if ledger is None:
        return None
    have = ledger.spans()
    found = [have[n]["seconds"] - have[n]["children_s"]
             for n in spans if n in have]
    return sum(found) if found else None
