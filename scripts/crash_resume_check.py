"""Crash-resume CI check: SIGKILL a checkpointed campaign, resume, compare.

    PYTHONPATH=src python scripts/crash_resume_check.py

The parent process never imports JAX: a device (a TPU chip) belongs to one
process at a time, so every campaign runs in a child of its own, and each
child exits before the next one starts.

1. ``--role reference`` runs the UNINTERRUPTED campaign and saves its
   final params, losses and per-round metrics,
2. ``--role victim`` runs the same campaign with ``checkpoint_every``
   armed (it marks each committed checkpoint and sleeps briefly after it,
   so the kill window is wide); the parent SIGKILLs it once the first
   mark appears — a real, unhandled kill mid-campaign,
3. ``--role resume`` resumes via ``resilience.resume_campaign`` and saves
   the same results,
4. the parent asserts the resumed results are BYTE-IDENTICAL to the
   uninterrupted reference.

Exit code 0 on success; any mismatch or timeout is a hard failure.  All
roles are this same file re-entered under ``subprocess`` so they share one
campaign definition.
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

ROUNDS = 24
CHECKPOINT_EVERY = 4
SEEDS = (0, 1)
FRAMEWORK = "fedavg"
SCENARIO = "faults:0.2"          # crash-resume under fault injection too
TIMEOUT_S = 300.0


def _setup():
    from repro.configs.splitme_dnn import DNNConfig
    from repro.core.cost import SystemParams
    from repro.data import oran

    cfg = DNNConfig(name="crash-check", n_features=30, n_classes=3,
                    hidden=(16, 16, 8), split_index=1)
    sp = SystemParams(M=8, seed=0)
    X, y = oran.generate(n_per_class=120, seed=0)
    (Xtr, ytr), _ = oran.train_test_split(X, y)
    clients = oran.partition_non_iid(Xtr, ytr, sp.M, samples_per_client=16,
                                     seed=0)
    kw = dict(rounds=ROUNDS, seeds=SEEDS, K=4, E=3, scenario=SCENARIO,
              scenario_seed=1)
    return cfg, sp, clients, kw


def _save(res, out: str) -> None:
    import jax
    leaves = {f"param_{i}": np.asarray(l)
              for i, l in enumerate(jax.tree.leaves(res.params))}
    np.savez(out, losses=res.losses,
             metrics=np.array([repr(m) for m in res.metrics]),
             skipped_rounds=res.skipped_rounds,
             crashed_rounds=res.crashed_rounds, **leaves)


def run_role(role: str, ckpt_dir: str, out: str) -> None:
    from repro.launch import campaign, resilience
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg, sp, clients, kw = _setup()
    if role == "reference":
        _save(campaign.run_campaign(FRAMEWORK, cfg, sp, clients, **kw), out)
    elif role == "victim":
        def committed(r):
            with open(out, "a") as f:        # the parent's kill signal
                f.write(f"{r}\n")
            time.sleep(0.5)
        campaign.run_campaign(FRAMEWORK, cfg, sp, clients,
                              checkpoint_every=CHECKPOINT_EVERY,
                              checkpoint_dir=ckpt_dir,
                              _checkpoint_hook=committed, **kw)
    else:
        _save(resilience.resume_campaign(
            FRAMEWORK, cfg, sp, clients, checkpoint_dir=ckpt_dir,
            checkpoint_every=CHECKPOINT_EVERY, **kw), out)


def _child(role: str, ckpt_dir: str, out: str) -> subprocess.Popen:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--role", role,
         "--ckpt-dir", ckpt_dir, "--out", out], env=env)


def _finish(proc: subprocess.Popen, role: str) -> bool:
    try:
        rc = proc.wait(timeout=TIMEOUT_S)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        print(f"[crash-resume] FAIL: {role} did not finish in {TIMEOUT_S}s")
        return False
    if rc != 0:
        print(f"[crash-resume] FAIL: {role} exited with {rc}")
    return rc == 0


def main() -> int:
    with tempfile.TemporaryDirectory(prefix="crash_resume_") as tmp:
        ckpt_dir = os.path.join(tmp, "ckpt")
        ref_out = os.path.join(tmp, "reference.npz")
        res_out = os.path.join(tmp, "resumed.npz")
        marks = os.path.join(tmp, "committed.txt")

        print("[crash-resume] reference (uninterrupted) campaign ...")
        if not _finish(_child("reference", ckpt_dir, ref_out), "reference"):
            return 1

        print("[crash-resume] launching victim subprocess ...")
        victim = _child("victim", ckpt_dir, marks)
        deadline = time.monotonic() + TIMEOUT_S
        while not os.path.exists(marks) and victim.poll() is None:
            if time.monotonic() > deadline:
                victim.kill()
                victim.wait()
                print(f"[crash-resume] FAIL: no checkpoint appeared in "
                      f"{TIMEOUT_S}s")
                return 1
            time.sleep(0.05)
        if victim.poll() is None:
            victim.send_signal(signal.SIGKILL)
            victim.wait()
            print("[crash-resume] SIGKILLed victim after its first "
                  "committed checkpoint")
        elif victim.returncode != 0:
            print(f"[crash-resume] FAIL: victim exited with "
                  f"{victim.returncode} before any checkpoint")
            return 1
        else:
            # lost the race — the campaign is tiny; resume is then a
            # restore-only pass, which the comparison still validates
            print("[crash-resume] victim finished before the kill; "
                  "resume degenerates to restore-only")

        print("[crash-resume] resuming ...")
        if not _finish(_child("resume", ckpt_dir, res_out), "resume"):
            return 1

        with np.load(ref_out) as ref, np.load(res_out) as res:
            if sorted(ref.files) != sorted(res.files):
                ok = False
            else:
                ok = all(np.array_equal(ref[k], res[k], equal_nan=k == "losses")
                         for k in ref.files)
            skipped, crashed = int(res["skipped_rounds"]), int(
                res["crashed_rounds"])
    if not ok:
        print("[crash-resume] FAIL: resumed campaign diverged from the "
              "uninterrupted reference")
        return 1
    print(f"[crash-resume] OK: resumed == uninterrupted "
          f"(byte-identical params/losses/metrics; "
          f"skipped_rounds={skipped}, crashed_rounds={crashed})")
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--role", choices=("reference", "victim", "resume"),
                    default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--out", default=None)
    ns = ap.parse_args()
    if ns.role:
        run_role(ns.role, ns.ckpt_dir, ns.out)
        sys.exit(0)
    sys.exit(main())
