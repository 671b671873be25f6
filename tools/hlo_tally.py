"""Name a fusion, or count a program's copies, in minutes: compile a cell's programs for a described v5e (no chip)
from the tree in the current directory, write compile().as_text() to hlo_out/<cell>[.<program>].hlo.txt under that
same directory (git-ignored; a parent's checkout and the change's each keep their own), print the path, and
tally the operations of the ENTRY computation — and of every `while` body, where a served program's decode steps
run — by opcode, result shape and whether the fusion they call holds a convolution (a matmul with an epilogue reads
as `fusion bf16[2048]` in a device trace's labels). A `copy` keeps its result's layout in the tally: a pool copied
whole between two layouts is `copy bf16[576,8,64,128]{3,1,2,0...}`.

A train cell gives its `jit_train_step`; a serving cell (drivers `serve`, `serve_moe`) gives every program of the
engine's `_program_inventory()` (`jit_serve_decode_chunk`, `jit_serve_unified_step`), the engine built over
parameter SHAPES with the deployment's sizes as the driver passes them.
python <repo>/tools/hlo_tally.py <cell> [min count to print] [layers, to compile a shallower model faster]"""
import collections, os, re, sys
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from benchmark import run as brun

cell = brun.load_cell(sys.argv[1])
least = int(sys.argv[2]) if len(sys.argv) > 2 else 1
m, dep = cell["config"], cell["config"]["deployment"]
if len(sys.argv) > 3:
    m["num_hidden_layers"] = int(sys.argv[3])
one = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
jax.config.update("jax_enable_compilation_cache", False)
sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)
abstract = lambda t: jax.tree.map(lambda a: sds(a.shape, a.dtype), t)
driver = cell["mix"]["driver"]
if driver == "train":
    from benchmark.drivers import train
    from benchmark.drivers.serve import llama_config
    step, params, opt, _ = train.build_train_step(llama_config(m, "bfloat16"), None, 0)
    x = sds((dep["batch"], dep["seq"]), jnp.int32)
    programs = [("", step.jitted, (abstract(params), abstract(opt), sds((), jnp.float32), x, x))]
    del params, opt
elif driver == "train_moe":
    from benchmark.drivers import train_moe
    _, make_step = train_moe.build_model(train_moe.model_config(m, "bfloat16"), 0)
    step, params, opt = make_step()
    ahead = m["num_nextn_predict_layers"]
    y = sds((dep["batch"], dep["seq"]), jnp.int32)
    programs = [("", step.jitted, (abstract(params), abstract(opt), sds((), jnp.float32),
                                   sds((dep["batch"], dep["seq"] + ahead), jnp.int32), y) + (y,) * ahead)]
    del params, opt
elif driver == "train_afmoe":
    from benchmark.drivers import train_afmoe
    _, make_step = train_afmoe.build_model(train_afmoe.model_config(m, "bfloat16"), 0)
    step, params, opt = make_step()
    x = sds((dep["batch"], dep["seq"]), jnp.int32)
    programs = [("", step.jitted, (abstract(params), abstract(opt), sds((), jnp.float32), x, x))]
    del params, opt
else:   # a serving cell: the engine over parameter shapes, sized as the cell's driver sizes it
    from paddle_tpu.serving import ContinuousBatchingEngine
    sizes = dict(slots=dep["slots"], max_prompt_len=dep["max_prompt_len"], max_new_tokens=dep["max_new_tokens"])
    if driver == "serve_moe":
        from benchmark.drivers import serve_moe
        from paddle_tpu.models.mellum import serving_param_shapes
        cfg = serve_moe.model_config(m, "bfloat16")
        shapes = serving_param_shapes(cfg)
        sizes.update(token_budget=dep["token_budget"], max_pages=dep["kv_pool_tokens"] // 64 + 1, logprobs=True)
    else:
        from benchmark import arith
        from benchmark.drivers import serve
        cfg, shapes = serve.llama_config(m, "bfloat16"), serve.weight_shapes(m)
        sizes.update(kv_pool_bytes=dep["kv_pool_tokens"] * arith.kv_bytes_per_token(m, 2))
    eng = ContinuousBatchingEngine(cfg, {k: jax.ShapeDtypeStruct(v, jnp.bfloat16) for k, v in shapes.items()}, **sizes)
    programs = [("." + name, fn, abstract(args)) for name, fn, args in eng._program_inventory()]
jax.default_backend = lambda: "tpu"


def tally_of(lines, comps):
    tally = collections.Counter()
    for line in lines:
        mm = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = (\(?[^ ]+(?:, [^ ]+)*\)?) ([\w\-]+)\(", line)
        if not mm:
            continue
        name, shape, op = mm.groups()
        shape = shape if op == "copy" else re.sub(r"\{[^}]*\}", "", shape)
        called = re.search(r"calls=%?([\w.\-]+)", line)
        conv = bool(called) and any(" convolution(" in l for l in comps.get(called.group(1), []))
        # a Mosaic kernel's instruction carries the kernel's `name=`
        kernel = " " + re.sub(r"\.\d+$", "", name) if "tpu_custom_call" in line else ""
        tally[(op + (" +conv" if conv else "") + kernel, shape)] += 1
    return tally


for suffix, fn, args in programs:
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    path = os.path.join(os.getcwd(), "hlo_out", f"{sys.argv[1]}{suffix}.hlo.txt")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)
    ma = compiled.memory_analysis()
    print(f"\n== {text.split(',')[0]}: {path}, {len(text)} bytes; temporaries {ma.temp_size_in_bytes / 2**30:.3f} GiB, live",
          (ma.argument_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes + ma.temp_size_in_bytes) / 2**30, "GiB")
    # computations by name -> body; the ENTRY's name; the bodies of the whiles
    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            cur = head.group(2); comps[cur] = []
            entry = cur if head.group(1) else entry
        elif cur is not None:
            comps[cur].append(line)
    bodies = sorted(set(re.findall(r" while\(.*body=%?([\w.\-]+)", text)))
    for where in [entry] + bodies:
        print(f"-- {'ENTRY' if where == entry else 'while body'} {where}")
        for (op, shape), n in sorted(tally_of(comps[where], comps).items(), key=lambda kv: -kv[1]):
            if n >= least and op not in ("parameter", "get-tuple-element", "bitcast", "constant", "tuple"):
                print(f"{n:5d}  {op:28s} {shape}")
