"""Drive the main path once on a TPU: compile VGG16-224, serve it over
HTTP through the Pallas kernels, and check what comes back.

Usage, from the root of a checkout on a machine with a TPU:

    python chip_smoke.py [--seed N]
    python chip_smoke.py --four-chips    # the sharded path, four chips

One process holds the chip and runs every phase:

  1. device   ``jax.devices()[0]`` must be a TPU; there is no CPU fallback.
  2. compile  VGG16-D at 224x224: pattern-pruned conv weights matching the
              paper's Table II ImageNet statistics plus a seeded FC head
              (``models.cnn.synthetic_vgg16``), through ``compile_network``
              with the structural verifier and range pass on.
  3. serve    ``classify_session(batch_slots=8)`` under ``ServingServer``;
              16 images POSTed to ``/v1/stream`` over a socket.
              Every request must be answered, the forward traced once,
              and its compiled program must hold one ``tpu_custom_call``
              per spmm layer (the kernel ran, not the XLA path or the
              interpreter).
  4. fp32     served logits vs ``cnn_apply`` on the same pruned weights at
              ``default_matmul_precision("highest")``.
  5. int8     the same, compiled at ``precision="int8"``, compared with the
              XLA int8 path (``backend="xla"``, same semantics) on the chip.

``--four-chips`` runs only the sharded path: the fp32 program partitioned
``model=4`` on a (1, 4) mesh and served through ``classify_session(mesh=)``,
against the same program on one chip.

This is a smoke run, not a benchmark: the times it prints include the
JSON transport and are read once.  The last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
any failed check exits non-zero without it.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
BATCH_SLOTS = 8
REQUESTS = 16
# max|engine - ref| / max|ref| over the served fp32 logits, against the
# float32 reference at "highest" matmul precision.  With fp32 products on
# both sides a v5e measured 5.1e-7; a kernel whose f32 dot runs as one
# bf16 pass (Mosaic's default) measured 5.6e-3 there, and fails.
FP32_REL_TOL = 1e-3
# The same for int8 Pallas against the XLA int8 path.  Both compute the
# same int8 products, but an ulp of fp32 difference upstream can flip the
# rounding of an activation in the next layer's per-row quantization, so
# they agree to a few int8 steps (1/127 ~ 7.9e-3 each), not bit for bit:
# 1.2e-2 measured on a v5e.  A wrong scale or brick is off by O(1).
INT8_REL_TOL = 5e-2


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def make_images(cfg, n: int, seed: int) -> np.ndarray:
    """8-bit pixels scaled to [-2, 2): exact in float32 and short in JSON."""
    rng = np.random.default_rng(seed)
    shape = (n, cfg.in_channels, cfg.input_hw, cfg.input_hw)
    return ((rng.integers(0, 256, shape) - 128) / 64.0).astype(np.float32)


def serve_over_http(session, images: np.ndarray, label: str):
    """Boot ``ServingServer`` on ``session``, stream ``images`` through
    ``POST /v1/stream``, return the logits in request order."""
    from repro.serve import ServingServer

    server = ServingServer(session, admit_wait_s=0.02)
    t0 = time.perf_counter()
    host, port = server.start_in_thread()  # warmup: trace + XLA compile
    warm_s = time.perf_counter() - t0
    try:
        body = json.dumps({"requests": [{"image": im.tolist()} for im in images]})
        t0 = time.perf_counter()
        conn = http.client.HTTPConnection(host, port, timeout=600)
        conn.request("POST", "/v1/stream", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        lines = [json.loads(ln) for ln in resp.read().splitlines() if ln]
        conn.close()
        serve_s = time.perf_counter() - t0
    finally:
        server.shutdown()
    n = len(images)
    answered = sorted(ln["index"] for ln in lines if ln.get("ok"))
    log(f"[{label}] warmup (trace + XLA compile) {warm_s:.1f} s; "
        f"{len(answered)}/{n} requests answered over HTTP in {serve_s:.2f} s "
        f"({n / serve_s:.2f} req/s, JSON transport included)")
    check(resp.status == 200, f"[{label}] /v1/stream returned {resp.status}")
    check(answered == list(range(n)), f"[{label}] not every request answered")
    tc = session.trace_count()
    log(f"[{label}] trace_count == {tc}")
    check(tc == 1, f"[{label}] forward traced {tc} times, expected 1")
    logits = np.zeros((n, len(lines[0]["logits"])), np.float32)
    for ln in lines:
        logits[ln["index"]] = ln["logits"]
    return logits


def count_kernels(session, program, label: str) -> str:
    """The served forward's compiled text must call the Pallas kernel once
    per spmm layer (every conv and the FC)."""
    text = session.backend.lower().compile().as_text()
    n = text.count('custom_call_target="tpu_custom_call"')
    want = len(program.convs) + 1
    log(f"[{label}] tpu_custom_call in compiled forward: {n} (want {want})")
    check(n == want, f"[{label}] {n} kernel calls, expected {want}")
    return text


def compile_program(cfg, params, bits, precision: str, label: str):
    from repro.engine import CompileOptions, compile_network

    t0 = time.perf_counter()
    prog = compile_network(
        cfg, params, bits,
        options=CompileOptions(precision=precision, verify="strict"),
    )
    log(f"[{label}] host compile (lower + verify + ranges) "
        f"{time.perf_counter() - t0:.2f} s")
    return prog


def in_batches(fn, images: np.ndarray) -> np.ndarray:
    """``fn`` over ``images`` in serving-sized batches (one shape, one
    compile, and the serving batch's memory footprint)."""
    import jax.numpy as jnp

    return np.concatenate([
        np.asarray(fn(jnp.asarray(images[i:i + BATCH_SLOTS])))
        for i in range(0, len(images), BATCH_SLOTS)
    ])


def agreement(a: np.ndarray, b: np.ndarray) -> tuple[float, float, float]:
    """(max-abs error, max-abs error over max|b|, top-1 agreement)."""
    err = float(np.abs(a - b).max())
    rel = err / float(np.abs(b).max())
    top1 = float((a.argmax(-1) == b.argmax(-1)).mean())
    return err, rel, top1


def one_chip(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.engine import make_forward
    from repro.models.cnn import cnn_apply, synthetic_vgg16
    from repro.serve import classify_session

    cfg, params, bits = synthetic_vgg16("imagenet", seed=seed)
    images = make_images(cfg, REQUESTS, seed)

    # fp32: the served Pallas forward vs the plain float32 reference
    prog = compile_program(cfg, params, bits, "fp32", "fp32")
    session = classify_session(prog, batch_slots=BATCH_SLOTS)
    got = serve_over_http(session, images, "fp32")
    count_kernels(session, prog, "fp32")
    ref_fn = jax.jit(lambda p, x: cnn_apply(cfg, p, x))
    with jax.default_matmul_precision("highest"):
        ref = in_batches(lambda x: ref_fn(params, x), images)
    err, rel, top1 = agreement(got, ref)
    log(f"[fp32] vs cnn_apply (highest): max-abs {err:.3e}, "
        f"max-rel {rel:.3e} (tol {FP32_REL_TOL:.0e}), top-1 agreement "
        f"{top1:.4f}")
    check(np.isfinite(got).all(), "[fp32] non-finite logits")
    check(rel <= FP32_REL_TOL, f"[fp32] max-rel error {rel:.3e} > tol")
    check(top1 == 1.0, f"[fp32] top-1 agreement {top1:.4f} < 1")
    del session

    # int8: the served int8 kernel vs the XLA int8 path on the same chip
    progq = compile_program(cfg, params, bits, "int8", "int8")
    session = classify_session(progq, batch_slots=BATCH_SLOTS)
    gotq = serve_over_http(session, images, "int8")
    count_kernels(session, progq, "int8")
    t0 = time.perf_counter()
    refq = in_batches(make_forward(progq, backend="xla"), images)
    log(f"[int8] XLA int8 reference forward (compile included) "
        f"{time.perf_counter() - t0:.1f} s")
    err, rel, top1 = agreement(gotq, refq)
    log(f"[int8] vs XLA int8 path: max-abs {err:.3e}, max-rel {rel:.3e} "
        f"(tol {INT8_REL_TOL:.0e}), top-1 agreement {top1:.4f}")
    _, _, top1_fp32 = agreement(gotq, got)
    log(f"[int8] top-1 agreement with the fp32 engine {top1_fp32:.4f} "
        "(random-init VGG16: not a check)")
    check(np.isfinite(gotq).all(), "[int8] non-finite logits")
    check(rel <= INT8_REL_TOL, f"[int8] max-rel error {rel:.3e} > tol")
    check(top1 == 1.0, f"[int8] top-1 agreement with XLA int8 {top1:.4f} < 1")


def four_chips(seed: int) -> None:
    import jax

    from repro.engine import partition_network
    from repro.launch.mesh import make_mesh
    from repro.models.cnn import synthetic_vgg16
    from repro.serve import classify_session

    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, got {len(devices)}")
    cfg, params, bits = synthetic_vgg16("imagenet", seed=seed)
    images = make_images(cfg, REQUESTS, seed)
    prog = compile_program(cfg, params, bits, "fp32", "fp32")

    single = classify_session(prog, batch_slots=BATCH_SLOTS)
    one = serve_over_http(single, images, "1 chip")
    del single

    mesh = make_mesh((1, 4), ("data", "model"), devices=devices)
    sharded = classify_session(
        partition_network(prog, data=1, model=4), batch_slots=BATCH_SLOTS,
        mesh=mesh,
    )
    four = serve_over_http(sharded, images, "4 chips")
    text = count_kernels(sharded, prog, "4 chips")
    n_ar = text.count(" all-reduce(") + text.count(" all-reduce-start(")
    log(f"[4 chips] all-reduce ops in the sharded program: {n_ar}")
    check(n_ar > 0, "[4 chips] sharded program has no all-reduce")
    in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
    log(f"[4 chips] bytes in use per device: {in_use}")
    # each device holds its quarter of the padded tile slabs
    slab = sum(op.bp.w_comp.nbytes for op in [*prog.convs, prog.fc]) // 4
    check(min(in_use) >= slab // 2,
          f"[4 chips] a device holds < {slab // 2} bytes: work not spread")
    err, rel, top1 = agreement(four, one)
    log(f"[4 chips] vs 1 chip: max-abs {err:.3e}, max-rel {rel:.3e} "
        f"(tol {FP32_REL_TOL:.0e}), top-1 agreement {top1:.4f}")
    check(rel <= FP32_REL_TOL, f"[4 chips] max-rel error {rel:.3e} > tol")
    check(top1 == 1.0, f"[4 chips] top-1 agreement {top1:.4f} < 1")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the synthetic weights and images")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on four chips")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(REPO, "src", "repro")):
        print(f"chip_smoke: no src/repro next to {__file__}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX runs on {dev.platform!r}, not a TPU; this "
              "script measures nothing off the chip", file=sys.stderr)
        return 1
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
        f"jax {jax.__version__}")
    try:
        (four_chips if args.four_chips else one_chip)(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    peak = [d.memory_stats().get("peak_bytes_in_use") for d in jax.devices()]
    log(f"peak device memory (bytes, per device): {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
