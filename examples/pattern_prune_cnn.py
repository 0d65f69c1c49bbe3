"""End-to-end reproduction in miniature: train -> pattern-prune -> map ->
simulate -> compile -> serve (the paper's full flowchart, Fig 3, CPU-sized,
plus the deployment path).

  PYTHONPATH=src python examples/pattern_prune_cnn.py \\
      [--precision {int8,fp32}] [--cell-bits N] [--trace-out trace.json]

Steps:
  1. train a small CNN on a synthetic 4-class task to ~100% accuracy,
  2. ADMM pattern pruning (irregular prune -> pattern PDF -> top-K
     dictionary -> ADMM -> hard projection -> masked retrain),
  3. map the pruned kernels with the kernel-reordering scheme,
  4. report the paper's three metrics on this network,
  5. compile the pruned network into an executable crossbar program and
     serve a batch of requests through the engine's classification service
     — then recompile with ``optimize='auto'`` to let the per-layer
     mapping design-space search shrink crossbar area at the same logits,
  6.-7. measured-vs-assumed energy pricing, sharded execution over a mesh,
  8. cell precision: recompile the same pruned network quantized.

Cell precision (step 8): the paper stores weights bit-sliced over 4-bit
RRAM cells; ``--precision int8`` compiles the pruned network a second
time with per-OU-row-group symmetric int8 weights that occupy
``ceil(8 / cell_bits)`` cells each (2 at the default ``--cell-bits 4``)
and *executes* them through the int8-input/int32-accumulate kernels.
That is the accuracy/area trade-off knob made measurable: the narrower
cells cut crossbar area and ADC energy (printed as the area/energy win
vs the fp32 compile), at the cost of a bounded quantization error —
printed as the max-abs logit delta and top-1 agreement vs the fp32
engine on a synthetic eval batch.  ``--precision fp32`` skips step 8;
``--cell-bits`` varies the priced cell width without touching the stored
int8 numbers (e.g. 2-bit cells -> 4 slices -> more area, same accuracy).

``--trace-out trace.json`` records steps 5+ on a span tracer
(``repro.obs``): compile phases, the served batches' ``service.*`` step
phases, and the served requests' lifecycles.  The script prints the
top-3 slowest compile phases and step phases, and the written file loads
in Perfetto or chrome://tracing.
"""

import argparse
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.mapping import map_layer, map_layer_naive
from repro.core.pruning import PruneConfig, admm_pattern_prune, sparsity_of
from repro.engine import (
    CompileOptions,
    InferenceService,
    compile_network,
    load_program,
    make_forward,
    partition_network,
    save_program,
)
from repro.launch.mesh import make_mesh
from repro.models.cnn import (
    cnn_apply,
    conv_weight_names,
    init_cnn,
    mini_cnn_config,
)
from repro.optim import adamw

ap = argparse.ArgumentParser(description=__doc__)
ap.add_argument("--precision", choices=["int8", "fp32"], default="int8",
                help="stored cell precision for the step-8 quantized "
                     "compile (fp32 skips it)")
ap.add_argument("--cell-bits", type=int, default=4,
                help="RRAM cell width the int8 weights are sliced over "
                     "for hardware pricing")
ap.add_argument("--trace-out", default=None, metavar="FILE",
                help="write a Chrome trace-event JSON of compile/serve "
                     "spans (open in Perfetto or chrome://tracing)")
args = ap.parse_args()
enable_compile_cache()
if args.trace_out:
    from repro.obs import Tracer

    tracer = Tracer()
else:
    tracer = None
# build the quantized-compile config up front so bad flags fail in
# milliseconds, not after the training/pruning pipeline has run
if args.precision != "fp32":
    quant_opts = CompileOptions(precision=args.precision,
                                cell_bits=args.cell_bits)

t0 = time.time()
cfg = mini_cnn_config(num_classes=4, input_hw=12, widths=(8, 16, 16))
protos = jax.random.normal(jax.random.PRNGKey(42), (4, 1, 12, 12))


def gen_batch(key, n=64):
    k1, k2 = jax.random.split(key)
    y = jax.random.randint(k1, (n,), 0, 4)
    x = protos[y] + 0.7 * jax.random.normal(k2, (n, 1, 12, 12))
    return x, y


def loss_fn(p, x, y):
    logits = cnn_apply(cfg, p, x)
    return -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(y.shape[0]), y])


def accuracy(p):
    accs = []
    k = jax.random.PRNGKey(999)
    for _ in range(8):
        k, sk = jax.random.split(k)
        x, y = gen_batch(sk, 256)
        accs.append(float((cnn_apply(cfg, p, x).argmax(-1) == y).mean()))
    return float(np.mean(accs))


# -- 1. dense training ------------------------------------------------------
params = init_cnn(cfg, jax.random.PRNGKey(0))
opt = adamw(weight_decay=0.0)
state = opt.init(params)


@jax.jit
def step(p, s, x, y):
    _, g = jax.value_and_grad(loss_fn)(p, x, y)
    return opt.update(g, s, p, 3e-3)


key = jax.random.PRNGKey(1)
for _ in range(400):
    key, sk = jax.random.split(key)
    params, state = step(params, state, *gen_batch(sk))
acc_dense = accuracy(params)
print(f"[{time.time()-t0:5.1f}s] dense accuracy: {acc_dense:.3f}")

# -- 2. ADMM pattern pruning -------------------------------------------------
names = conv_weight_names(cfg)


def data_iter():
    k = jax.random.PRNGKey(7)
    while True:
        k, sk = jax.random.split(k)
        yield gen_batch(sk)


pcfg = PruneConfig(target_sparsity=0.7, num_patterns=4, admm_steps=200,
                   retrain_steps=200)
res = admm_pattern_prune(params, names, loss_fn, data_iter(), pcfg, opt)
acc_pruned = accuracy(res.params)
print(f"[{time.time()-t0:5.1f}s] pattern-pruned accuracy: {acc_pruned:.3f} "
      f"(drop {acc_dense-acc_pruned:+.3f}), "
      f"sparsity {sparsity_of(res.params, names):.1%}")
for n in names:
    d = res.dictionaries[n]
    print(f"  {n}: {d.num_nonzero_patterns} nonzero patterns, "
          f"layer sparsity {res.layer_sparsity(n):.1%}")

# -- 3./4. mapping + metrics --------------------------------------------------
tot_ours = tot_naive = 0
for n in names:
    bits = res.pattern_bits[n]
    m = map_layer(bits)
    nv = map_layer_naive(bits.shape[0], bits.shape[1])
    tot_ours += m.num_crossbars
    tot_naive += nv.num_crossbars
print(f"crossbars: ours={tot_ours} naive={tot_naive} "
      f"-> area efficiency {tot_naive/max(tot_ours,1):.2f}x")

# -- 5. compile into an executable crossbar program + serve ------------------
program = compile_network(cfg, res.params, res.pattern_bits,
                          options=CompileOptions(tracer=tracer))
with tempfile.TemporaryDirectory() as td:  # pay compilation once per model
    program = load_program(save_program(td + "/prog", program))
x, y = gen_batch(jax.random.PRNGKey(123), 64)
logits_ref = cnn_apply(cfg, res.params, x)
logits_eng = make_forward(program)(x)
diff = float(jnp.abs(logits_eng - logits_ref).max())
rep = program.hardware_report()
print(f"[{time.time()-t0:5.1f}s] compiled program "
      f"(max |engine - dense| = {diff:.2e}):")
for op, detail in program.op_list():
    print(f"  {op}: {detail}")
print(f"  hardware: {rep['crossbars']} crossbars "
      f"(naive {rep['naive_crossbars']}), "
      f"energy {rep['energy_pj']/1e3:.1f} nJ/img, "
      f"index {rep['index_kb']:.2f} KiB")

# -- 5b. mapping design-space search ------------------------------------------
# The paper fixes one geometry (512x512 crossbars, pattern-order packing)
# for every layer; optimize='auto' searches per layer over crossbar dims
# and packing/reorder strategies, priced by the simulator's own cost
# model, and never chooses a candidate worse than the fixed scheme on
# area or energy.  A reorder is layout only: the fp32 logits agree to
# rounding (each layer's order also orders the next layer's sums).
program_opt = compile_network(
    cfg, res.params, res.pattern_bits,
    options=CompileOptions(optimize="auto", tracer=tracer),
)
rep_opt = program_opt.hardware_report()
logits_opt = make_forward(program_opt)(x)
assert bool(jnp.allclose(logits_opt, logits_eng, rtol=1e-5, atol=1e-5)), \
    "layout changed math"
print(f"[{time.time()-t0:5.1f}s] optimize='auto' mapping search:")
for name, m_entry in rep_opt["mapping"]["per_layer"].items():
    print(f"  {name}: {m_entry['rows']}x{m_entry['cols']} crossbars, "
          f"block_order={m_entry['block_order']}, "
          f"reorder={m_entry['reorder']}")
print(f"  area {rep_opt['area_cells']} cells vs fixed {rep['area_cells']} "
      f"({rep['area_cells']/max(rep_opt['area_cells'],1):.1f}x win), "
      f"energy {rep_opt['energy_pj']/1e3:.1f} nJ/img "
      f"(fixed {rep['energy_pj']/1e3:.1f}), logits equal to fp32 rounding")

service = InferenceService(program, batch_slots=16, collect_stats=True,
                           tracer=tracer)
labels = service.classify(np.asarray(x))
acc_served = float((labels == np.asarray(y)).mean())
m = service.metrics
print(f"[{time.time()-t0:5.1f}s] served {len(labels)} requests in "
      f"{service.batches_run} batches, accuracy {acc_served:.3f}")
print(f"  scheduler: 1 traced batch shape ({service.trace_count()} trace), "
      f"occupancy {m['occupancy_mean']:.0%}, "
      f"mean latency {m['latency_mean_s']*1e3:.1f} ms")

# -- 6. measured vs assumed energy --------------------------------------------
# The service counted, per layer and OU row-group, how often an input
# selection was all-zero on the traffic it actually served; pricing from
# those *measured* skip probabilities replaces the assumed-probability
# fallback (here 0.5 — "ReLU zeroes about half").
rep_m = service.hardware_report(assumed_skip=0.5)
skip = rep_m["skip"]
print(f"energy pricing over {skip['measured_windows']} measured windows:")
print(f"  no-skip upper bound : {skip['energy_pj_noskip']/1e3:8.1f} nJ/img")
print(f"  assumed skip (p=0.5): {skip['energy_pj_assumed']/1e3:8.1f} nJ/img")
print(f"  measured skip       : {skip['energy_pj_measured']/1e3:8.1f} nJ/img "
      f"({skip['measured_discount']:.1%} below no-skip)")
print(f"  measured - assumed  : "
      f"{skip['measured_vs_assumed_delta_pj']/1e3:+8.1f} nJ/img "
      f"({skip['measured_vs_assumed_delta_frac']:+.1%})")
for lrow in rep_m["layers"]:
    st = service.activation_stats.layers.get(lrow["name"])
    if st is None:
        continue
    print(f"  {lrow['name']}: mean measured skip {st.mean_skip():.2f}, "
          f"energy {lrow['energy_pj_measured']/1e3:.1f} nJ "
          f"(no-skip {lrow['energy_pj']/1e3:.1f} nJ)")
# -- 7. sharded execution across a device mesh -------------------------------
# One compiled artifact serves from multiple chips: each layer's spmm
# tiles split over the mesh's 'model' axis (partial outputs psum-combined)
# and batch slots over 'data'.  On this host the mesh covers however many
# devices exist (run under XLA_FLAGS=--xla_force_host_platform_device_count=8
# to see a real 8-way split); outputs match the unsharded forward.
n_dev = len(jax.devices())
mesh = make_mesh((1, n_dev), ("data", "model"))
sharded_prog = partition_network(program, model=n_dev)
logits_sh = make_forward(sharded_prog, mesh=mesh)(x)
print(f"[{time.time()-t0:5.1f}s] sharded over {n_dev} device(s): "
      f"max |sharded - unsharded| = "
      f"{float(jnp.abs(logits_sh - logits_eng).max()):.2e}")
chips = sharded_prog.hardware_report()["chips"]
print(f"  per-chip split ({chips['model_shards']} tile-parallel chip(s)): "
      f"max {chips['crossbars_per_chip_max']:.1f} crossbars/chip, "
      f"bottleneck {chips['cycles_parallel']:.0f} cycles "
      f"({chips['parallel_speedup']:.2f}x vs single chip)")

# -- 8. cell precision: int-quantized 4-bit-cell execution --------------------
# The same pruned network, stored the way the crossbars would hold it:
# per-row-group symmetric int8 bricks sliced over args.cell_bits-wide
# cells, executed through the int8-input/int32-accumulate kernels.  The
# hardware report now prices the cells actually stored, so the area and
# ADC-energy win of the narrower cells appears next to the accuracy cost.
if args.precision != "fp32":
    program_q = compile_network(
        cfg, res.params, res.pattern_bits, options=quant_opts
    )
    x_eval, y_eval = gen_batch(jax.random.PRNGKey(321), 256)
    logits_fp = make_forward(program)(x_eval)
    logits_q = make_forward(program_q)(x_eval)
    top1_agree = float(
        (jnp.argmax(logits_q, -1) == jnp.argmax(logits_fp, -1)).mean()
    )
    acc_q = float((np.asarray(jnp.argmax(logits_q, -1)) ==
                   np.asarray(y_eval)).mean())
    rep_q = program_q.hardware_report()
    prec = rep_q["precision"]
    cb_fp, _ = program.weight_bytes()
    cb_q, _ = program_q.weight_bytes()
    print(f"[{time.time()-t0:5.1f}s] cell precision "
          f"({prec['weights']}, {prec['cell_bits']}-bit cells, "
          f"{prec['cells_per_weight']} cells/weight):")
    print(f"  accuracy: max |int8 - fp32| = "
          f"{float(jnp.abs(logits_q - logits_fp).max()):.2e}, "
          f"top-1 agreement {top1_agree:.1%} "
          f"(served accuracy {acc_q:.3f})")
    print(f"  area:     {rep_q['crossbars']} crossbars vs "
          f"{rep['crossbars']} fp32-priced "
          f"({rep['crossbars']/max(rep_q['crossbars'],1):.2f}x win), "
          f"weights {cb_q/1024:.1f} KiB vs {cb_fp/1024:.1f} KiB")
    print(f"  energy:   {rep_q['energy_pj']/1e3:.1f} nJ/img vs "
          f"{rep['energy_pj']/1e3:.1f} nJ/img no-skip "
          f"({rep['energy_pj']/max(rep_q['energy_pj'],1e-9):.2f}x win)")

# -- observability epilogue: where the time actually went --------------------
# The slowest compile phases and serving-step phases fall straight out of
# the collected spans.
if tracer is not None:
    PHASES = ("prune", "reorder", "pack", "quantize")
    top_phases = [(n, s) for n, s in tracer.slowest(16, cat="compile")
                  if n in PHASES][:3]
    print("  top-3 compile phases: "
          + ", ".join(f"{n} {s*1e3:.1f} ms" for n, s in top_phases))
    top_steps = [(n, s) for n, s in tracer.slowest(8, cat="serve")
                 if n != "service.step"][:3]
    print("  top-3 step phases:    "
          + ", ".join(f"{n.removeprefix('service.')} {s*1e3:.1f} ms"
                      for n, s in top_steps))
    tracer.write(args.trace_out)
    print(f"  wrote {args.trace_out} (open in Perfetto / chrome://tracing)")

print("(full-scale VGG16 numbers: PYTHONPATH=src python -m benchmarks.run"
      " --only paper; engine bench: python -m benchmarks.bench_engine)")
