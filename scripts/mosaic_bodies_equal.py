#!/usr/bin/env python3
"""Two StableHLO files of ``scripts/stablehlo_sums.py`` that differ only in
their Mosaic kernels' serialized bodies: are the kernels the same program?

    JAX_PLATFORMS=cpu python3 scripts/mosaic_bodies_equal.py <parent.mlir> <change.mlir>

A kernel's body carries the source locations of its operations, so an edit
that moves a kernel's lines inside its file (another function grown above it)
changes the bytes of every program that holds the kernel though nothing it
computes changed. This parses each body (MLIR bytecode, base64 in the custom
call's ``backend_config``), prints it without debug locations and compares;
and compares the rest of the two files with the bodies cut out.
"""
import re, base64, sys, json
from jax._src.lib.mlir import ir
from jax._src.lib import tpu as tpu_dialect  # registers mosaic dialect
import jax._src.tpu_custom_call  # noqa
def bodies(path):
    text=open(path).read()
    out=[]
    for m in re.finditer(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', text):
        out.append(base64.b64decode(m.group(1)))
    rest=re.sub(r'\\22body\\22: \\22[A-Za-z0-9+/=]+\\22','BODY',text)
    return out, rest
def strip(b):
    ctx=ir.Context()
    ctx.allow_unregistered_dialects=True
    try:
        tpu_dialect.register_dialect(ctx)
    except Exception as e:
        pass
    with ctx:
        m=ir.Module.parse(b)
        return m.operation.get_asm(enable_debug_info=False)
a,ra=bodies(sys.argv[1]); b,rb=bodies(sys.argv[2])
print("bodies", len(a), len(b), "rest equal", ra==rb)
for i,(x,y) in enumerate(zip(a,b)):
    if x==y: print(i,"bytes equal"); continue
    sx,sy=strip(x),strip(y)
    print(i,"stripped equal", sx==sy, len(sx))
