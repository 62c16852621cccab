"""What a field product, a curve formula and an EC kernel cost, one tree at a time.

The probe behind PERF.md's per-primitive tables (PR 32). Two ways to read one
checkout of this repo, each primitive jitted by itself at ``(rows, 16)``:

``--mode hlo``   NO CHIP. Compiles for a DESCRIBED TPU v5e with the TPU compiler
    that is installed beside JAX and reads the optimised HLO: seconds to compile,
    bytes of lowered text, and the element-wise instructions inside the fusions
    by opcode (``multiply`` first: a 64-bit limb product lowers as four 32-bit
    multiplies with their carries, a 32-bit one as one), the number of fusions
    (each is a program step of its own, with a fixed cost of ~1,800 cycles by the
    compiler's reckoning) and the sum of the compiler's own ``estimated_cycles``.
    Counts say which way a change goes, never how far.
``--mode chip``  Times the same programs on the attached chip: each primitive is
    applied ``--chain`` times inside ONE program (a ``fori_loop`` whose carry is
    the primitive's own output, as the ladders' scans do), so that a dispatch's
    ~1 ms does not drown a ~50 us product; the reading is ms per application,
    three rounds of ``--reps`` calls. ``--kernels`` adds the two production
    kernels (``verify_core_hybrid_wide``, ``verify_core_split``) at ``--rows``
    rows, verdicts held against what the rows were built to be.

One process reads ONE tree (``--tree DIR``, default this checkout): run it once a
tree, parent and change in the same chip call, and compare the JSON files.
``--variant FILE[,FILE]`` executes each FILE with ``F`` bound to the tree's
``corda_tpu.ops.field`` after import, for a candidate product that is a patch of
that module and not yet the tree's own. ``fieldsteps_variants/atadd.py`` is one: the rows
placed by slice update again, the form PR 32 measured 5x slower.

    # chip-free, seconds a primitive:
    python corda_tpu/tools/fieldsteps.py --mode hlo --out /tmp/hlo.json
    # on the chip, parent against change, one call:
    python corda_tpu/tools/fieldsteps.py --mode chip --tree _parent --out chiprun_out/p.json
    python corda_tpu/tools/fieldsteps.py --mode chip --kernels --out chiprun_out/c.json

Recipe of the chip-free mode (it has to run before JAX is imported):
``JAX_PLATFORMS=cpu TPU_WORKER_HOSTNAMES=localhost``,
``jax.experimental.topologies.get_topology_desc(platform="tpu",
topology_name="v5e:2x2")``, a ``ShapeDtypeStruct`` with
``SingleDeviceSharding(topo.devices[0])``, ``jax.jit(f).lower(..).compile()``.
One process at a time can load the TPU compiler.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys
import time

ELEMENTWISE = ("multiply", "add", "subtract", "and", "or", "xor", "shift-left",
               "shift-right-logical", "shift-right-arithmetic", "select",
               "compare", "convert", "negate", "not")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mode", choices=("hlo", "chip"), required=True)
    ap.add_argument("--tree", default=None, help="root of the checkout to read")
    ap.add_argument("--variant", default=None, help="file(s) that patch F, comma-separated")
    ap.add_argument("--rows", type=int, default=8192)
    ap.add_argument("--chain", type=int, default=64)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--only", default=None, help="comma-separated primitives")
    ap.add_argument("--kernels", action="store_true")
    ap.add_argument("--kernels-only", action="store_true")
    ap.add_argument("--out", required=True)
    return ap.parse_args(argv)


def hlo_counts(text: str) -> dict:
    """Instructions of the optimised module by opcode: every instruction
    inside a fused computation and every top-level one, constants and
    parameters left out."""
    ops = collections.Counter(
        m.group(1) for m in re.finditer(
            r"^\s+(?:ROOT )?%?[\w.\-]+ = [^=]*? ([a-z][a-z\-]*)\(", text, re.M))
    for skip in ("parameter", "constant", "get-tuple-element", "tuple",
                 "bitcast", "fusion"):
        ops.pop(skip, None)
    out = {"instructions": sum(ops.values()),
           "elementwise": sum(ops[k] for k in ELEMENTWISE),
           "fusions": len(re.findall(r"^\s+(?:ROOT )?%?[\w.\-]+ = [^=]*? fusion\(",
                                     text, re.M))}
    out["estimated_cycles"] = sum(
        int(c) for c in re.findall(r'"estimated_cycles":"(\d+)"', text))
    out.update({k: ops[k] for k in ("multiply", "pad", "slice", "concatenate",
                                    "dynamic-update-slice", "scatter", "copy")
                if ops[k]})
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    root = os.path.abspath(args.tree or os.path.join(os.path.dirname(__file__),
                                                     "..", ".."))
    sys.path.insert(0, root)
    if args.mode == "hlo":
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from corda_tpu.ops import ed25519 as ed
    from corda_tpu.ops import field as F
    from corda_tpu.ops import weierstrass as wc
    assert os.path.abspath(F.__file__).startswith(root), F.__file__
    for patch in (args.variant or "").split(","):
        if patch:
            exec(compile(open(patch).read(), patch, "exec"),
                 {"F": F, "jax": jax, "jnp": jnp, "np": np})
    rows = args.rows
    k1 = wc.CURVES["secp256k1"]
    b3 = 3 * k1.b % k1.p

    # name -> (n field elements carried, n held fixed, step(carry, fixed) -> carry)
    prims = {
        "mul.k1": (1, 1, lambda c, f: (F.mul(c[0], f[0], F.PSECP),)),
        "sqr.k1": (1, 0, lambda c, f: (F.sqr(c[0], F.PSECP),)),
        "sub.k1": (1, 1, lambda c, f: (F.sub(c[0], f[0], F.PSECP),)),
        "mul.25519": (1, 1, lambda c, f: (F.mul(c[0], f[0], F.P25519),)),
        "sqr.25519": (1, 0, lambda c, f: (F.sqr(c[0], F.P25519),)),
        "k1.dbl": (3, 0, lambda c, f: wc.dbl(c, k1)),
        "k1.add": (3, 3, lambda c, f: wc._add_k1(c, f, k1.p, b3)),
        "k1.madd": (3, 2, lambda c, f: wc._madd_k1(c, f, k1.p, b3)),
        "ed.double": (4, 0, lambda c, f: ed.double(c)),
        "ed.add_cached": (4, 4, lambda c, f: ed.add_cached(c, f)),
    }
    if args.only:
        prims = {k: v for k, v in prims.items() if k in args.only.split(",")}
    if args.kernels_only:
        prims = {}

    if args.mode == "hlo":
        from jax.experimental import topologies
        from jax.experimental.compilation_cache import compilation_cache as cc
        from jax.sharding import SingleDeviceSharding
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        where = SingleDeviceSharding(topo.devices[0])
        device = topo.devices[0].device_kind
    else:
        from corda_tpu.utils.compile_cache import enable_compile_cache
        enable_compile_cache()
        where = None
        device = jax.devices()[0].device_kind
        assert jax.devices()[0].platform == "tpu", jax.devices()

    def shape(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=where)

    result = {"tree": root, "variant": args.variant, "mode": args.mode,
              "device": device, "rows": rows, "chain": args.chain,
              "reps": args.reps, "primitives": {}, "kernels": {}}

    def dump():
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(result, fh, indent=1)

    def build(name, fn, example):
        """Lower and compile ``fn`` for ``example``'s shapes; the record."""
        t0 = time.perf_counter()
        lowered = jax.jit(fn).lower(*[shape(a) for a in example])
        t1 = time.perf_counter()
        compiled = lowered.compile()
        rec = {"lower_s": round(t1 - t0, 2),
               "compile_s": round(time.perf_counter() - t1, 2),
               "hlo_bytes": len(lowered.as_text())}
        if args.mode == "hlo":
            rec.update(hlo_counts(compiled.as_text()))
        return compiled, rec

    def timeit(compiled, example, per_call):
        out = compiled(*example)
        jax.block_until_ready(out)
        ms = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(args.reps):
                out = compiled(*example)
            jax.block_until_ready(out)
            ms.append(round((time.perf_counter() - t0) / args.reps / per_call * 1e3, 5))
        return ms, out

    rng = np.random.default_rng(32)

    def element():
        return rng.integers(0, 1 << 16, size=(rows, F.NLIMB), dtype=np.uint64)

    for name, (n_carry, n_fixed, step) in prims.items():
        carry = tuple(element() for _ in range(n_carry))
        fixed = tuple(element() for _ in range(n_fixed))
        if args.mode == "hlo":      # the primitive alone: what one application is made of
            def fn(*a, _step=step, _n=n_carry):
                return _step(tuple(a[:_n]), tuple(a[_n:]))
            per_call = 1
        else:
            def fn(*a, _step=step, _n=n_carry):
                return jax.lax.fori_loop(
                    0, args.chain, lambda _, c: tuple(_step(c, tuple(a[_n:]))),
                    tuple(a[:_n]))
            per_call = args.chain
        try:
            compiled, rec = build(name, fn, carry + fixed)
            if args.mode == "chip":
                example = [jax.device_put(a) for a in carry + fixed]
                rec["ms"], _ = timeit(compiled, example, per_call)
        except Exception as e:      # a candidate may refuse a formula's operands
            rec = {"error": f"{type(e).__name__}: {e}"[:300]}
        result["primitives"][name] = rec
        print("primitive", name, rec, flush=True)
        dump()

    if args.kernels or args.kernels_only:
        programs, want = kernel_programs(rows)
        for name, (fn, example) in programs.items():
            example = [np.asarray(a) if args.mode == "hlo" else jax.device_put(a)
                       for a in example]
            compiled, rec = build(name, fn, example)
            if args.mode == "chip":
                rec["ms"], verdicts = timeit(compiled, example, 1)
                rec["verdicts_right"] = bool((np.asarray(verdicts) == want).all())
            result["kernels"][name] = rec
            print("kernel", name, rec, flush=True)
            dump()
    dump()
    print(json.dumps(result))
    return 0


def kernel_programs(rows: int):
    """``({name: (program, prepared arguments)}, verdicts wanted)`` of the two
    production kernels at ``rows`` rows: 64 signers' signatures tiled, one row
    in 64 corrupted. Imports the tree ``main`` put first on ``sys.path``."""
    import numpy as np

    from corda_tpu.core.crypto import ecmath
    from corda_tpu.ops import ed25519 as ed
    from corda_tpu.ops import weierstrass as wc
    rng = np.random.default_rng(3232)
    want = np.ones(rows, bool)
    want[::64] = False

    ed_base = []
    for _ in range(64):
        sk, msg = rng.bytes(32), rng.bytes(32)
        pub = ecmath.ed25519_public_key(sk)
        ed_base.append((pub, ecmath.ed25519_sign(sk, msg, pub), msg))
    items = [ed_base[i % 64] for i in range(rows)]
    for i in range(0, rows, 64):
        pub, sig, msg = items[i]
        items[i] = (pub, sig[:9] + bytes([sig[9] ^ 1]) + sig[10:], msg)
    *ed_args, precheck = ed.prepare_batch_split(items, ed.SPLIT_B_WINDOW)
    assert precheck.all()

    k1 = ecmath.SECP256K1
    k1_base = []
    for _ in range(64):
        priv = int.from_bytes(rng.bytes(32), "little") % (k1.n - 1) + 1
        msg = rng.bytes(32)
        k1_base.append((k1.mul(priv, k1.g), msg, *ecmath.ecdsa_sign(k1, priv, msg)))
    items = [k1_base[i % 64] for i in range(rows)]
    for i in range(0, rows, 64):
        pub, msg, r, s = items[i]
        items[i] = (pub, msg + b"!", r, s)
    *k1_args, precheck = wc.prepare_batch_hybrid_wide(items, wc.HYBRID_G_WINDOW)
    assert precheck.all()

    return {
        "verify_core_hybrid_wide": (
            lambda *a: wc.verify_core_hybrid_wide(*a, g_w=wc.HYBRID_G_WINDOW), k1_args),
        "verify_core_split": (
            lambda *a: ed.verify_core_split(*a, w=ed.SPLIT_B_WINDOW), ed_args),
    }, want


if __name__ == "__main__":
    sys.exit(main())
