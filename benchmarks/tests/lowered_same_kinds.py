#!/usr/bin/env python3
"""Builder's tool, no chip: ``lowered_same.py`` for the configurations it
cannot lower, those whose programs take a second cache (window pools and
page tables) or state pools: lower (not compile) the decode step and a
prefill chunk for a DESCRIBED v5e and print the SHA-256 of each program's
StableHLO text, Mosaic kernels included, with program locations as the
engine sets them (``jaxenv.PROGRAM_LOCATIONS``: without them a kernel's body
carries the checkout's path and two trees never agree).

    cd <tree>; JAX_PLATFORMS=cpu python <repo>/benchmarks/tests/lowered_same_kinds.py \
        mimo-v2-flash-7l granite-4.0-h-micro

It imports ``dynamo_tpu`` and the configurations from the CURRENT directory:
run it from a ``git archive`` of the parent and from the change, and compare
the lines. Not part of any check.
"""
import os, sys, json, hashlib
os.environ.setdefault("TPU_LOG_DIR","disabled"); os.environ["JAX_PLATFORMS"]="cpu"
sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from benchmarks.harness.catalog import Catalog
from dynamo_tpu.models import llama
from dynamo_tpu.engine.cache import cache_kinds, WindowPages
from dynamo_tpu.parallel.mesh import serving_mesh
jax.config.update("jax_enable_compilation_cache", False)
from dynamo_tpu.utils.jaxenv import PROGRAM_LOCATIONS
for _n,_v in PROGRAM_LOCATIONS.items(): jax.config.update(_n,_v)
dev = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0]
sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=SingleDeviceSharding(dev))
mesh = serving_mesh(1, devices=[dev])
i32=jnp.int32
for name in sys.argv[1:]:
    config = Catalog().data("configs", name)
    eng = config["benchmark"]["engine"]
    hf = {k:v for k,v in config.items() if k!="benchmark"}
    cfg = llama.LlamaConfig.from_hf_config(hf)
    page,B,C = eng["page_size"], eng["max_batch"], eng["prefill_chunk"]
    S = 2048
    shapes = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda a: sds(a.shape,a.dtype), shapes)
    kinds = cache_kinds(cfg)
    ks, vs = kinds[0].pool_shapes(200, page)
    kp, vp = sds(ks,cfg.dtype), sds(vs,cfg.dtype)
    out={"config":name}
    if cfg.has_window:
        wks, wvs = kinds[1].pool_shapes(100, page)
        wk, wv = sds(wks,cfg.dtype), sds(wvs,cfg.dtype)
        Pw = WindowPages.chunk_read_pages(cfg.sliding_window, C, page)
        dec = lambda p,t,k,v,pt,ln,wk,wv,wt: llama.forward_decode(p,cfg,t,k,v,pt,ln,attn_impl="pallas",mesh=mesh,win=(wk,wv,wt),stats={})
        dargs=(params,sds((B,),i32),kp,vp,sds((B,S//page),i32),sds((B,),i32),wk,wv,sds((B,S//page),i32))
        pre = lambda p,t,pos,k,v,wi,rpg,rp,rv,li,wk,wv,ww,wpg,wpos,wval: llama.forward(p,cfg,t,pos,k,v,wi,None,rp,rv,attn_impl="flash",mesh=mesh,logits_idx=li,read_pages=rpg,stats={},win=(wk,wv,ww,wpg,wpos,wval))
        pargs=(params,sds((1,C),i32),sds((1,C),i32),kp,vp,sds((1,C),i32),sds((1,S//page),i32),sds((1,S),i32),sds((1,S),jnp.bool_),sds((1,),i32),wk,wv,sds((1,C),i32),sds((1,Pw),i32),sds((1,Pw*page),i32),sds((1,Pw*page),jnp.bool_))
    else:
        ss, cs = kinds[1].state_shapes(B)
        sp, cp = sds(ss,jnp.float32), sds(cs,cfg.dtype)
        dec = lambda p,t,k,v,pt,ln,sp,cp,act: llama.forward_decode(p,cfg,t,k,v,pt,ln,attn_impl="pallas",mesh=mesh,ssm=(sp,cp,act))
        dargs=(params,sds((B,),i32),kp,vp,sds((B,S//page),i32),sds((B,),i32),sp,cp,sds((B,),jnp.bool_))
        pre = lambda p,t,pos,k,v,wi,rpg,rp,rv,li,sp,cp,lanes,reset,nv: llama.forward(p,cfg,t,pos,k,v,wi,None,rp,rv,attn_impl="flash",mesh=mesh,logits_idx=li,read_pages=rpg,ssm=(sp,cp,lanes,reset,nv))
        pargs=(params,sds((1,C),i32),sds((1,C),i32),kp,vp,sds((1,C),i32),sds((1,S//page),i32),sds((1,S),i32),sds((1,S),jnp.bool_),sds((1,),i32),sp,cp,sds((1,),i32),sds((1,),jnp.bool_),sds((1,),i32))
    for what,fn,args in (("decode_step",dec,dargs),("prefill_chunk",pre,pargs)):
        txt = jax.jit(fn).lower(*args).as_text()
        out[what]=hashlib.sha256(txt.encode()).hexdigest()
    print(json.dumps(out), flush=True)
