"""First chip command of a bring-up: two small REAL compiles, memory after each.

    chiprun -- python tools/chip_probe.py

Run it before `chip_smoke.py` is paid for (ISSUE 22, Tentpole 1): it
prints the chip host's MemTotal and core count, resident memory and
`device.memory_stats()` after each of two pairing-kernel compiles
(agg_fast_verify_msm_idx 4x4 over a 64-key registry; multi_verify_msm at
bucket 4), what `malloc_trim` gives back THERE (the first compile runs
with the trim held back), each kernel's verdicts valid/forged with
donation as the backend defaults it on the chip, warm call seconds, and
whether `block_until_ready` waits. Run twice in one command, the second
run shows whether the persistent cache is hit. PR 22 wrote it and never
got a chip to run it on. One process; needs a TPU.
"""
import json, os, resource, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax, numpy as np
import chip_smoke as S
from grandine_tpu.runtime.warmup import enable_persistent_cache
from grandine_tpu.tpu import compile_scope
from grandine_tpu.crypto import bls as A
from grandine_tpu.crypto.constants import R
from grandine_tpu.crypto.hash_to_curve import hash_to_g2
from grandine_tpu.metrics import Metrics
from grandine_tpu.tpu import bls as B
from grandine_tpu.tpu.registry import DevicePubkeyRegistry

def say(**kw): print(json.dumps(kw), flush=True)
def mem(dev):
    st = dev.memory_stats() or {}
    return dict(rss=S._rss(), ru_maxrss=resource.getrusage(resource.RUSAGE_SELF).ru_maxrss*1024,
                dev_in_use=st.get("bytes_in_use"), dev_peak=st.get("peak_bytes_in_use"), dev_limit=st.get("bytes_limit"))
t00 = time.time()
dev = jax.devices()[0]
say(platform=dev.platform, kind=dev.device_kind, count=len(jax.devices()), jax=jax.__version__,
    mem_total=S._mem_total(), cpus=os.cpu_count(), cache=enable_persistent_cache(),
    cache_env=os.environ.get("JAX_COMPILATION_CACHE_DIR"), init_s=round(time.time()-t00,1), **mem(dev))
assert dev.platform == "tpu", "this probe reads a chip host; it has no CPU path"
# hold the trim back so its effect on THIS host can be read
real_trim = compile_scope._malloc_trim
compile_scope._malloc_trim = None
backend = B.TpuBlsBackend(metrics=Metrics())
say(donate=backend.donate_buffers)
a, b = S._progression(1, 0)
pts = S.make_keys(64, a, b)
reg = DevicePubkeyRegistry()
t0 = time.time(); reg.ensure(tuple(A.g1_to_bytes(p) for p in pts)); say(step="registry64", s=round(time.time()-t0,2), **mem(dev))
msgs = [b"probe-%d" % j for j in range(2)]
hs = [hash_to_g2(m) for m in msgs]
members = [[0, 1, 2, 3], [4, 5, 6, 7]]
sigs = [A.Signature(h.mul(sum(a + b*i for i in ix) % R)) for h, ix in zip(hs, members)]
for rep in range(3):
    t0 = time.time()
    ok = backend.fast_aggregate_verify_batch_indexed_async(msgs, sigs, members, reg)()
    say(step="agg_idx_4x4", rep=rep, ok=bool(ok), s=round(time.time()-t0,3), compile_total=compile_scope.totals(), **mem(dev))
    if rep == 0:
        r0 = S._rss(); compile_scope._malloc_trim = real_trim; compile_scope.trim_host_memory(); say(step="trim", rss_before=r0, rss_after=S._rss())
bad = [sigs[0], A.Signature(sigs[1].point + hs[1])]
say(step="agg_idx_forged", ok=bool(backend.fast_aggregate_verify_batch_indexed_async(msgs, bad, members, reg)()))
# compile 2: flat multi_verify, 4 distinct messages
m4 = [b"flat-%d" % j for j in range(4)]
h4 = [hash_to_g2(m) for m in m4]
pk4 = [A.PublicKey(p) for p in pts[:4]]
s4 = [A.Signature(h.mul((a + b*i) % R)) for i, h in enumerate(h4)]
for rep in range(3):
    t0 = time.time()
    ok = backend.multi_verify_async(m4, s4, pk4)()
    say(step="multi_verify_4", rep=rep, ok=bool(ok), s=round(time.time()-t0,3), compile_total=compile_scope.totals(), **mem(dev))
s4b = [s4[0], s4[1], A.Signature(s4[2].point + h4[0]), s4[3]]
say(step="multi_verify_forged", ok=bool(backend.multi_verify_async(m4, s4b, pk4)()))
# the old runtime's claims: does block_until_ready wait? are identical executions deduped?
fn = jax.jit(lambda x: (x @ x).sum())
x = jax.device_put(np.ones((4096, 4096), np.float32)); fn(x).block_until_ready()
t0 = time.time(); y = fn(x); t_disp = time.time()-t0; y.block_until_ready(); t_block = time.time()-t0; float(y); t_read = time.time()-t0
t0 = time.time(); y = fn(x); y.block_until_ready(); t_same = time.time()-t0
say(step="block_until_ready", dispatch_s=round(t_disp,5), blocked_s=round(t_block,5), read_s=round(t_read,5), same_args_again_s=round(t_same,5))
say(step="done", total_s=round(time.time()-t00,1), kernels=sorted(k for k in B._JITTED), **mem(dev))
