#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls —
``python -m dlbb_tpu.cli {e2e,train,serve,bench1d}`` with the YAML configs
committed under ``dlbb_tpu/configs/chip_*.yaml`` — at the full width of the
repo's own models, and checks what comes out by the repo's own means:

one chip    forward  7B, real attention, bf16, batch 8 x seq 512
            train    1B, Adam (bf16 moments), dots remat, 1 + 4 steps
            serve A  7B widths, 16 layers, 24 Poisson requests, defaults
            serve B  the same with the fused-scan / in-flight / chunked-
                     prefill fast path
four chips  (whenever four or more devices are found, after the above)
            bench1d  the eight reference collectives over 4 ranks at the
                     "16MB" label, each checked against the numpy oracle
            forward  7B on tp=4;  train 1B on dp2 x tp2, ZeRO-1
            serve    on the auto plan (tp=4) and on dp2 x tp2

This process never imports JAX: a chip belongs to one process at a time,
so each phase is a child that gets the chip alone and a clean HBM.  The
parent reads each child's exit code and JSON artifacts; any check that
does not hold is a non-zero exit.  There is no CPU path: the first child
reports the platform and anything but ``tpu`` ends the run.

``python chip_smoke.py --rehearse N`` is the only other mode, and an
explicit request, not a fallback: the same phases at toy widths on the
N-device CPU-simulated mesh, every line labelled ``REHEARSAL (cpu)`` — for
debugging the command before chip time is spent, and for the tier-1 test
that keeps this file from rotting.

Everything written goes under ``chiprun_out/chip_smoke/`` (the directory
the chip tool brings back; ``chip_smoke_rehearsal/`` for a rehearsal, each
run replacing the last) and the compile cache
(``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``); neither is
committed.  The last line of stdout is one JSON object.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
CONFIGS = REPO / "dlbb_tpu" / "configs"
OUT = REPO / "chiprun_out"  # / chip_smoke, or / chip_smoke_rehearsal
BUDGET_SECONDS = 1150.0  # the contract allows 1200, compilation included

TRAFFIC = ["--trace", "poisson", "--requests", "24", "--rate", "8"]
FAST_PATH = ["--decode-horizon", "16", "--inflight-window", "2",
             "--prefill-chunk", "128"]

# --rehearse: toy widths and lengths laid over the committed configs
TOY = {
    "model": {"hidden_size": 64, "num_layers": 2, "num_heads": 4,
              "ffn_intermediate": 128},
    "input": {"batch_size": 8, "sequence_length": 128},
    "serving": {"max_batch": 16, "max_seq": 128, "block_size": 16},
    # 4 bf16 steps at the real 1e-4 move a toy model's loss by less than
    # bf16 resolves
    "training": {"learning_rate": 1.0e-2},
}
TOY_FAST_PATH = ["--decode-horizon", "16", "--inflight-window", "2",
                 "--prefill-chunk", "32"]
TOY_SIZE = ("1KB", 256)   # bench1d label and per-rank elements

PROBE = """
import json, sys
n = int(sys.argv[1])
if n:
    from dlbb_tpu.utils.simulate import force_cpu_simulation
    force_cpu_simulation(n)
from dlbb_tpu.utils.sysinfo import collect_system_info
with open(sys.argv[2], "w") as f:
    json.dump(collect_system_info(), f)
"""


class Smoke:
    def __init__(self, rehearse: int) -> None:
        self.rehearse = rehearse
        self.tag = "REHEARSAL (cpu) " if rehearse else ""
        self.platform = "cpu" if rehearse else "tpu"
        self.out = OUT / ("chip_smoke_rehearsal" if rehearse
                          else "chip_smoke")
        self.t0 = time.monotonic()
        self.failures: list[str] = []
        self.phase = ""

    # -- output ----------------------------------------------------------

    def say(self, text: str = "") -> None:
        print(f"{self.tag}{text}", flush=True)

    def expect(self, ok: bool, what: str) -> None:
        self.say(f"  [{'ok' if ok else 'FAIL'}] {what}")
        if not ok:
            self.failures.append(f"{self.phase}: {what}")

    # -- children --------------------------------------------------------

    def child(self, argv: list[str], log_name: str) -> int:
        """Run one child to its end (or to the budget's), output to a log
        under ``self.out``.  The child leads its own process group, and the
        group is killed if anything is left of it."""
        remaining = BUDGET_SECONDS - (time.monotonic() - self.t0)
        if remaining <= 0:
            raise SystemExit(f"{self.tag}out of time before {log_name}")
        log_path = self.out / log_name
        with open(log_path, "w") as log:
            proc = subprocess.Popen(
                [sys.executable, *argv], cwd=REPO, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True,
            )
            try:
                rc = proc.wait(timeout=remaining)
            finally:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()
        if rc != 0:
            tail = log_path.read_text(errors="replace").splitlines()[-25:]
            for line in tail:
                self.say(f"  | {line}")
        return rc

    def cli(self, name: str, argv: list[str]) -> tuple[bool, Path]:
        """One phase = one ``python -m dlbb_tpu.cli ...`` child writing
        into its own directory."""
        self.phase = name
        out = self.out / name
        argv = [*argv, "--output", str(out)]
        if self.rehearse:
            argv += ["--simulate", str(self.rehearse)]
        self.say(f"== {name}: python -m dlbb_tpu.cli {' '.join(argv)}")
        t0 = time.monotonic()
        rc = self.child(["-m", "dlbb_tpu.cli", *argv], f"{name}.log")
        self.expect(rc == 0, f"exit code {rc} "
                             f"({time.monotonic() - t0:.0f} s)")
        return rc == 0, out

    def config(self, name: str) -> str:
        """The committed config, or in rehearsal its toy-width copy."""
        path = CONFIGS / name
        if not self.rehearse:
            return str(path.relative_to(REPO))
        import yaml

        cfg = yaml.safe_load(path.read_text())
        for section, overrides in TOY.items():
            if section in cfg:
                cfg[section].update(overrides)
        toy = self.out / "toy_configs" / name
        toy.parent.mkdir(parents=True, exist_ok=True)
        toy.write_text(yaml.safe_dump(cfg))
        return str(toy)

    # -- what every artifact must say --------------------------------------

    def device_report(self, art: dict, devices: int) -> None:
        info = art["system_info"]
        self.expect(info["backend"] == self.platform,
                    f"system_info.backend == {self.platform!r} "
                    f"(got {info['backend']!r})")
        cache = info["compile_cache"]
        self.say(f"  compile {art.get('compile_time_s', float('nan')):.1f} s;"
                 f" persistent cache {cache['hits']} hit(s), "
                 f"{cache['misses']} miss(es) in {cache['dir']}")
        peaks = [(d["memory_stats"] or {}).get("peak_bytes_in_use")
                 for d in info["devices"]]
        self.say("  peak_bytes_in_use per device: " + ", ".join(
            "not reported" if p is None else f"{p / 1e9:.2f} GB"
            for p in peaks))
        if self.rehearse:
            return
        used = sorted(p for p in peaks if p)[-devices:]
        self.expect(len(used) == devices,
                    f"{devices} device(s) report peak_bytes_in_use > 0")
        if devices > 1 and used:
            self.expect(used[-1] <= 4 * used[0],
                        "per-device peak bytes of the same order "
                        f"(max/min {used[-1] / max(used[0], 1):.2f})")

    @staticmethod
    def load_one(out: Path, pattern: str) -> dict:
        (path,) = sorted(out.glob(pattern))
        return json.loads(path.read_text())

    # -- phases ------------------------------------------------------------

    def forward(self, name: str, config: str, devices: int) -> None:
        ok, out = self.cli(name, ["e2e", "--config", self.config(config)])
        if not ok:
            return
        art = self.load_one(out, "xla_tpu_*.json")
        self.device_report(art, devices)
        check = art["output_check"]
        self.expect(check["finite"] and check["mean_abs"] > 0,
                    f"output finite and non-zero (shape {check['shape']}, "
                    f"mean |y| {check['mean_abs']:.4g})")
        self.expect(check["devices"] == devices,
                    f"output spread over {devices} device(s) "
                    f"(got {check['devices']})")
        if not self.rehearse:
            self.expect(art["mosaic_calls"] >= 1,
                        "compiled step calls the Mosaic flash kernel "
                        f"(tpu_custom_call x{art['mosaic_calls']}), not "
                        "the dense einsum")

    def train(self, name: str, config: str, zero: int, devices: int
              ) -> None:
        ok, out = self.cli(name, ["train", "--config", self.config(config),
                                  "--zero", str(zero)])
        if not ok:
            return
        art = self.load_one(out, "train_*.json")
        self.device_report(art, devices)
        losses = art["losses"]
        self.expect(len(losses) == 4
                    and all(x == x and abs(x) != float("inf")
                            for x in losses),
                    f"4 steps taken, every loss finite {losses}")
        self.expect(losses[-1] < losses[0],
                    f"last loss below first ({losses[0]:.4f} -> "
                    f"{losses[-1]:.4f})")
        self.expect(art["param_devices"] == devices,
                    f"parameters spread over {devices} device(s) "
                    f"(got {art['param_devices']})")
        if not self.rehearse:
            self.expect(art["mosaic_calls"] >= 3,
                        "compiled step calls the flash forward and both "
                        "backward kernels (tpu_custom_call "
                        f"x{art['mosaic_calls']})")

    def serve(self, name: str, config: str, extra: list[str],
              devices: int, fast_path: bool) -> None:
        """``devices`` 0 = whatever the auto plan (no parallelism section)
        makes of the host: (1, 1) on one chip, tp=4 on four."""
        ok, out = self.cli(name, ["serve", "--config", self.config(config),
                                  *TRAFFIC, *extra])
        if not ok:
            return
        art = self.load_one(out, "serving_chip_*.json")
        mesh = art["mesh"]
        devices = devices or mesh["dp"] * mesh["tp"]
        self.device_report(art, devices)
        req, res = art["requests"], art["resilience"]
        done = sum(1 for v in req["outcomes"].values() if v == "completed")
        self.expect(done == 24 and req["rejected"] == 0,
                    f"{done} of 24 requests completed, "
                    f"{req['rejected']} rejected")
        self.expect(res["retries"] == 0 and res["failed_requests"] == 0
                    and res["hung_dispatches"] == 0,
                    f"retries {res['retries']}, failed "
                    f"{res['failed_requests']}, hung dispatches "
                    f"{res['hung_dispatches']} (a carry reset leaves one "
                    "of them non-zero)")
        self.expect(art["param_devices"] == devices
                    and mesh["dp"] * mesh["tp"] == devices,
                    f"weights spread over {devices} device(s) (dp "
                    f"{mesh['dp']} x tp {mesh['tp']}, got "
                    f"{art['param_devices']})")
        if fast_path:
            fast = art["fast_path"]
            self.expect(fast["fused_scans"] > 0
                        and fast["prefill_chunks"] > 0,
                        f"fused scans {fast['fused_scans']}, prefill "
                        f"chunks {fast['prefill_chunks']}")
        # the budget prices the cache only; say what is really resident.
        # peak_bytes_in_use counts live buffers (a factor of two here would
        # be a carry that donation failed to alias); the compiled programs'
        # own temporaries are not in it
        model = art["config"]["model"]
        h, f = model["hidden_size"], model["ffn_intermediate"]
        weights = (model["num_layers"] * (4 * h * h + 2 * h * f) * 2
                   // mesh["tp"])
        cache = art["hbm"]["kv_cache_bytes_per_device"]
        peak = max(((d["memory_stats"] or {}).get("peak_bytes_in_use") or 0)
                   for d in art["system_info"]["devices"])
        if peak:
            self.say(f"  per device: weights {weights / 1e9:.2f} GB + cache "
                     f"{cache / 1e9:.2f} GB = "
                     f"{(weights + cache) / 1e9:.2f} GB; peak live "
                     f"buffers {peak / 1e9:.2f} GB "
                     f"(x{peak / (weights + cache):.2f})")

    def collectives(self, ranks: int) -> None:
        # the reference's "16MB" label is 4,194,304 bf16 elements: 8 MiB
        label, elements = TOY_SIZE if self.rehearse else ("16MB", 4_194_304)
        ok, out = self.cli("bench1d_r4", ["bench1d", "--ranks", str(ranks),
                                          "--sizes", label])
        if ok:
            arts = [json.loads(p.read_text()) for p in
                    sorted(out.glob(f"*_ranks{ranks}_{label}.json"))]
            self.expect(len(arts) == 8,
                        f"8 reference collectives measured ({len(arts)})")
            self.expect(all(a["system_info"]["backend"] == self.platform
                            for a in arts),
                        f"every artifact says backend {self.platform!r}")
            manifest = json.loads(
                (out / "sweep_manifest.json").read_text())
            cache = manifest["compile_cache"]
            self.say(f"  compile {manifest['compile_seconds_total']:.1f} s;"
                     f" persistent cache {cache['persistent_hits']} hit(s),"
                     f" {cache['persistent_misses']} miss(es) in "
                     f"{cache['dir']}")
        # bench1d only times; the oracle child checks each result once
        self.phase = "oracle_r4"
        report = self.out / "oracle_r4.json"
        argv = ["-m", "dlbb_tpu.comm.oracle", "--ranks", str(ranks),
                "--output", str(report), "--num-elements", str(elements)]
        if self.rehearse:
            argv += ["--simulate", str(self.rehearse)]
        self.say(f"== oracle_r4: python {' '.join(argv)}")
        rc = self.child(argv, "oracle_r4.log")
        self.expect(rc == 0, f"exit code {rc}: each op's result agrees "
                             "with the numpy oracle")
        if rc == 0:
            checks = json.loads(report.read_text())["checks"]
            self.expect(len(checks) == 8
                        and all(c["devices"] == ranks for c in checks),
                        f"8 results, each spread over {ranks} devices")

    # -- the run -----------------------------------------------------------

    def probe(self) -> dict:
        """Ask a child what JAX sees (this process must not touch JAX)."""
        report = self.out / "probe.json"
        rc = self.child(["-c", PROBE, str(self.rehearse), str(report)],
                        "probe.log")
        if rc != 0:
            raise SystemExit(f"{self.tag}device probe failed (exit {rc})")
        info = json.loads(report.read_text())
        self.say(f"platform {info['backend']}  device_kind "
                 f"{info['device_kind']}  devices {info['num_devices']}")
        self.say(f"jax {info['jax_version']}  jaxlib "
                 f"{info['jaxlib_version']}  libtpu {info['libtpu_version']}")
        for d in info["devices"]:
            self.say(f"  device {d['id']} coords {d['coords']}")
        if info["backend"] != self.platform:
            raise SystemExit(
                f"{self.tag}platform is {info['backend']!r}, not "
                f"{self.platform!r}: no accelerator, nothing to prove")
        return info

    def run(self) -> int:
        shutil.rmtree(self.out, ignore_errors=True)
        self.out.mkdir(parents=True)
        info = self.probe()
        fast = TOY_FAST_PATH if self.rehearse else FAST_PATH
        self.forward("forward_7b", "chip_forward_7b.yaml", 1)
        self.train("train_1b", "chip_train_1b.yaml", 0, 1)
        # on a four-chip host these two are the tp=4 serve runs
        self.serve("serve_default", "chip_serve_7b.yaml", [], 0, False)
        self.serve("serve_fastpath", "chip_serve_7b.yaml", fast, 0, True)
        if info["num_devices"] >= 4:
            self.collectives(4)
            self.forward("forward_7b_tp4", "chip_forward_7b_tp4.yaml", 4)
            self.train("train_1b_dp2_tp2", "chip_train_1b_dp2_tp2.yaml",
                       1, 4)
            self.serve("serve_dp2_tp2", "chip_serve_7b_dp2_tp2.yaml", fast,
                       4, True)
        result = {
            "ok": not self.failures,
            "device": {"platform": info["backend"],
                       "kind": info["device_kind"],
                       "count": info["num_devices"]},
        }
        if self.rehearse:
            result["rehearsal"] = self.tag.strip()
        if self.failures:
            result["failed"] = self.failures
            for f in self.failures:
                self.say(f"FAILED {f}")
        self.say(f"{time.monotonic() - self.t0:.0f} s in all")
        print(json.dumps(result), flush=True)
        return 0 if not self.failures else 1


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--rehearse", type=int, default=0, metavar="N",
                    help="toy widths on the N-device CPU-simulated mesh")
    return Smoke(ap.parse_args().rehearse).run()


if __name__ == "__main__":
    sys.exit(main())
