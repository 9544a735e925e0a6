"""GPipe pipeline: loss + grads match the non-pipelined reference."""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, devices: int = 4, timeout: int = 900) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.distributed.sharding import make_mesh
    """) + textwrap.dedent(body)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout,
        env={**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")})
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_gpipe_matches_reference_loss_and_grads():
    out = run_sub("""
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_config
        from repro.data import SyntheticCorpus
        from repro.distributed.pipeline import gpipe_loss, stage_slice
        from repro.models import transformer as tf
        from repro.models.layers import rmsnorm_apply
        from repro.train import trainer

        cfg = get_config("qwen2-1.5b").reduced(n_layers=4, vocab_size=256)
        key = jax.random.PRNGKey(0)
        params = tf.init_params(key, cfg, jnp.float32)
        corpus = SyntheticCorpus(cfg.vocab_size, seed=0)
        M, B, S = 4, 2, 32
        batch = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[jax.tree.map(jnp.asarray, corpus.batch(i, B, S))
              for i in range(M)])

        # reference: mean CE over microbatches, no pipeline
        def ref_loss(params):
            losses = []
            for i in range(M):
                mb = jax.tree.map(lambda a: a[i], batch)
                ce, _ = tf.forward_loss(params, cfg, mb, ce_chunk=S)
                losses.append(ce)
            return jnp.mean(jnp.stack(losses))

        ref, ref_grads = jax.value_and_grad(ref_loss)(params)

        # pipeline: 4 stages x 1 layer (full-manual 1-D pipe mesh)
        mesh = make_mesh((4,), ("pipe",))
        n_stages = 4

        def block_fn(stage_blocks, x):
            def body(x, bp):
                x, _, _ = tf._block_fwd(bp, x, cfg,
                                        jnp.arange(S)[None, :], 0, False)
                return x, None
            x, _ = jax.lax.scan(body, x, stage_blocks)
            return x

        def embed_fn(io_params, mb):
            x, _ = tf.embed_inputs(io_params, cfg, mb)
            return x

        def head_loss_fn(io_params, x, mb):
            x = rmsnorm_apply(io_params["final_norm"], x, cfg.norm_eps)
            logits = tf.unembed_apply(io_params["embed"], x, cfg)
            return tf.cross_entropy(logits, mb["labels"])

        pl = gpipe_loss(block_fn, embed_fn, head_loss_fn, axis="pipe")

        io_params = {"embed": params["embed"],
                     "final_norm": params["final_norm"]}
        blocks = params["blocks"]

        def pipelined(blocks, io_params):
            # stage axis: reshape stacked (L, ...) -> (P, L/P, ...)
            staged = jax.tree.map(
                lambda a: a.reshape(n_stages, a.shape[0] // n_stages,
                                    *a.shape[1:]), blocks)
            f = jax.shard_map(
                pl, mesh=mesh,
                in_specs=(P("pipe"), P(), P()),
                out_specs=P(),
                check_vma=False)
            return f(staged, io_params, batch)

        val, grads = jax.value_and_grad(pipelined, argnums=(0, 1))(
            blocks, io_params)
        print("ref", float(ref), "pipe", float(val))
        assert abs(float(ref) - float(val)) < 1e-4

        # grads: blocks + embedding
        d_blocks = max(float(jnp.abs(a - b).max()) for a, b in zip(
            jax.tree.leaves(grads[0]), jax.tree.leaves(ref_grads["blocks"])))
        d_emb = float(jnp.abs(grads[1]["embed"]["embedding"]
                              - ref_grads["embed"]["embedding"]).max())
        print("d_blocks", d_blocks, "d_emb", d_emb)
        assert d_blocks < 1e-4 and d_emb < 1e-4
        print("ok")
    """)
    assert "ok" in out
