"""Command-line entry point.

≙ reference CLI layer (SURVEY §1-L8): DeepLearning4jDistributedApp
(args4j master/worker flags, DeepLearning4jDistributedApp.java:60), YARN
Client, shell launchers.  In the SPMD world every host runs the same
program, so "master/worker" collapses into ``--process-id``/``--coordinator``
for ``jax.distributed`` plus the shared training command.

Usage:
  python -m deeplearning4j_tpu train --model lenet --epochs 2
  python -m deeplearning4j_tpu train --coordinator host:8476 --num-processes 4 --process-id 1
  python -m deeplearning4j_tpu bench
  python -m deeplearning4j_tpu status --port 9090
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time


def _add_distributed_flags(p: argparse.ArgumentParser) -> None:
    p.add_argument("--coordinator", default=None, help="host:port of process 0")
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)


def _start_status_rest(svc, args) -> None:
    """Start the status/control REST server when --status-port is given,
    printing a reachable URL (0.0.0.0 binds display as loopback)."""
    if args.status_port is None:
        return
    port = svc.start_rest_api(
        args.status_port, host=args.status_host,
        auth_token=getattr(args, "status_token", None),
    )
    shown = "127.0.0.1" if args.status_host == "0.0.0.0" else args.status_host
    print(f"status REST on http://{shown}:{port}/statetracker")
    if svc.auth_token is not None:
        if getattr(args, "status_token", None) is not None:
            # operator supplied the secret themselves — they know it;
            # don't repeat it onto stdout (often captured into logs)
            print("control POSTs require X-Auth-Token (as passed via "
                  "--status-token)")
        else:
            print(
                "control POSTs require X-Auth-Token: "
                f"{svc.auth_token[:8]}… (full secret in "
                f"{getattr(svc, 'auth_token_file', '<token file>')}, "
                "mode 0600)"
            )


def _transformer_cfg_from_args(args):
    """ONE flags->TransformerConfig recipe shared by train and the
    generate fallback — if the train-side conventions (byte vocab,
    d_ff=4*d_model, max_len=seq_len+1) ever change, pre-config
    checkpoint restore must change with them, not silently diverge."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=256,
        d_model=args.d_model,
        n_heads=args.n_heads,
        n_layers=args.n_layers,
        d_ff=4 * args.d_model,
        max_len=args.seq_len + 1,
        n_experts=args.n_experts,
        use_flash=getattr(args, "flash", False),
        remat=getattr(args, "remat", False),
        compute_dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
    )


def _transformer_cfg_from_file(path: str):
    """A ``TransformerConfig`` from a JSON file of its fields, or from
    the ``model`` group of a benchmark configuration file
    (``benchmark/configs/<name>.json``): the model a cell runs, by the
    same fields."""
    import json

    from deeplearning4j_tpu.models.transformer import TransformerConfig

    with open(path) as f:
        fields = json.load(f)
    return TransformerConfig.from_json(json.dumps(fields.get("model", fields)))


def _train_transformer(args) -> int:
    """Byte-level char-LM training for the flagship transformer: composed
    dp x tp mesh (``--tp``), optional MoE experts / FSDP, checkpointing via
    the npz or orbax backend, and a sampled continuation at the end."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pathlib import Path

    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig,
        lm_optimizer,
        transformer_generate,
        transformer_train_step,
    )
    from deeplearning4j_tpu.parallel import mesh as mesh_lib
    from deeplearning4j_tpu.parallel.cluster import ClusterService

    tp = max(1, args.tp)
    if args.d_model % args.n_heads:
        print(
            f"--d-model ({args.d_model}) must be divisible by --n-heads "
            f"({args.n_heads})", file=sys.stderr,
        )
        return 2
    if args.n_heads % tp:
        print(
            f"--n-heads ({args.n_heads}) must be divisible by --tp ({tp})",
            file=sys.stderr,
        )
        return 2
    if args.n_experts and args.n_experts != tp:
        print(
            f"--n-experts ({args.n_experts}) must equal --tp ({tp}): "
            "experts live one-per-device on the model axis",
            file=sys.stderr,
        )
        return 2

    if args.text:
        try:
            data = Path(args.text).read_bytes()
        except OSError as e:
            print(f"cannot read --text corpus: {e}", file=sys.stderr)
            return 2
    else:  # offline demo corpus
        data = (
            b"the quick brown fox jumps over the lazy dog. "
            b"pack my box with five dozen liquor jugs. "
        ) * 300
    arr = np.frombuffer(data, np.uint8).astype(np.int32)
    if len(arr) < args.seq_len + 2:
        print("corpus shorter than --seq-len", file=sys.stderr)
        return 2

    n_dev = len(jax.devices())
    dp = max(1, n_dev // tp)
    mesh = mesh_lib.dp_mp_mesh(dp, tp)
    cfg = _transformer_cfg_from_args(args)
    step, init_state, shard_tokens = transformer_train_step(
        mesh, cfg,
        optimizer=lm_optimizer(total_steps=args.steps),
        fsdp=args.fsdp,
    )
    params, opt_state = init_state(jax.random.key(0))

    mgr = None
    if args.checkpoint_dir:
        if args.checkpoint_backend == "npz" and jax.process_count() > 1:
            # the npz backend gathers every leaf to host via np.asarray;
            # in a multi-process run TP/FSDP-sharded leaves are not fully
            # addressable and the first save would raise deep inside jax.
            # Fail fast with the fix instead.
            print(
                "npz checkpoints cannot address multi-process shardings; "
                "use --checkpoint-backend orbax for distributed runs",
                file=sys.stderr,
            )
            return 2
        if args.checkpoint_backend == "orbax":
            from deeplearning4j_tpu.parallel.checkpoint import (
                AsyncShardedCheckpointManager,
            )

            mgr = AsyncShardedCheckpointManager(
                args.checkpoint_dir, save_every=args.save_every
            )
        else:
            from deeplearning4j_tpu.parallel.checkpoint import CheckpointManager

            mgr = CheckpointManager(
                args.checkpoint_dir, save_every=args.save_every
            )

    svc = ClusterService()
    svc.model_description = (
        f"transformer d_model={cfg.d_model} n_layers={cfg.n_layers} "
        f"n_heads={cfg.n_heads} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
        f"seq_len={args.seq_len} experts={cfg.n_experts} fsdp={args.fsdp}"
    )
    _start_status_rest(svc, args)
    svc.phase = "train"

    rng = np.random.default_rng(0)
    batch = max(dp, args.batch - args.batch % dp)
    svc.minibatch = batch
    loss = l = None
    for i in range(args.steps):
        # live batch-size control: POST /statetracker/minibatch changes
        # the sampled batch (rounded to the dp axis; a new shape means
        # one re-jit on the next step) — ≙ the reference's POST
        # minibatch resource
        posted = max(dp, svc.minibatch - svc.minibatch % dp)
        if posted != batch:
            batch = posted
            print(f"minibatch -> {batch} (REST)")
        starts = rng.integers(0, len(arr) - args.seq_len - 1, batch)
        toks = np.stack([arr[s : s + args.seq_len + 1] for s in starts])
        params, opt_state, l = step(
            params, opt_state, shard_tokens(jnp.asarray(toks))
        )
        svc.batches_so_far = i + 1
        # materialize the loss only on the print/save cadence — a float()
        # every step would sync the host and defeat async dispatch
        on_cadence = (i + 1) % 20 == 0 or (
            mgr is not None and (i + 1) % args.save_every == 0
        )
        if on_cadence or i + 1 == args.steps:
            loss = float(l)
            if (i + 1) % 20 == 0:
                print(f"step {i + 1}/{args.steps} loss {loss:.4f}")
            # report_loss returns True for patience exhaustion AND for a
            # POSTed /statetracker/earlystop
            if svc.report_loss(loss):
                print("early stop triggered")
                break
        if mgr:
            # the config rides in the meta so `generate` can rebuild the
            # restore template without re-plumbing the model flags
            # (≙ the reference persisting json config WITH the params —
            # MultiLayerConfiguration.toJson:125)
            mgr.maybe_save(
                i + 1, params, {"loss": loss, "config": cfg.to_json()}
            )
    if mgr is not None and hasattr(mgr, "wait"):
        mgr.wait()  # async saves must be durable before exit
    if loss is None and l is not None:
        loss = float(l)
    svc.phase = "done"
    print(f"final loss {loss:.4f}")

    if cfg.max_len >= 32:
        # the sample runs on the training mesh, and transformer_generate
        # takes no mesh: the Pallas decode kernel would sit bare in a
        # multi-device jit, which the TPU lowering refuses. The dense
        # path is the one GSPMD partitions.
        gen = transformer_generate(
            dataclasses.replace(cfg, decode_kernel=False)
        )
        prompt = jnp.asarray(arr[None, :16])
        out = gen(
            jax.device_get(params) if args.fsdp else params,
            prompt, jax.random.key(1),
            min(cfg.max_len - 16, 48), temperature=0.8, top_k=40,
        )
        text = bytes(np.asarray(out[0], np.uint8).tolist())
        print("sample:", text.decode("latin-1"))
    return 0


def cmd_train(args) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    if args.coordinator:
        from deeplearning4j_tpu.parallel.cluster import initialize_distributed

        initialize_distributed(args.coordinator, args.num_processes, args.process_id)

    if args.model == "transformer":
        return _train_transformer(args)

    from deeplearning4j_tpu.datasets import fetchers
    from deeplearning4j_tpu.parallel import DataParallelTrainer, data_parallel_mesh
    from deeplearning4j_tpu.parallel.checkpoint import CheckpointManager
    from deeplearning4j_tpu.parallel.cluster import ClusterService

    if args.model == "lenet":
        from deeplearning4j_tpu.models.lenet import build_lenet, lenet_loss

        net, params = build_lenet()
        loss_fn = lenet_loss(net)
        ds = fetchers.mnist(n=args.examples)
    elif args.model == "alexnet":
        from deeplearning4j_tpu.models.alexnet import build_alexnet, synthetic_cifar
        from deeplearning4j_tpu.models.lenet import lenet_loss

        net, params = build_alexnet()
        loss_fn = lenet_loss(net)
        ds = synthetic_cifar(args.examples)
    else:
        print(f"unknown model {args.model}", file=sys.stderr)
        return 2

    svc = ClusterService()
    _start_status_rest(svc, args)
    mesh = data_parallel_mesh()
    trainer = DataParallelTrainer(loss_fn, mesh=mesh)
    state = trainer.init(params)
    mgr = CheckpointManager(args.checkpoint_dir, save_every=args.save_every) if args.checkpoint_dir else None

    svc.phase = "train"
    n = ds.num_examples()
    b = min(args.batch, n)
    step_idx = 0
    for epoch in range(args.epochs):
        for batch in ds.batches(b, drop_last=True):
            x, y = trainer.shard_batch(jnp.asarray(batch.features), jnp.asarray(batch.labels))
            state, loss = trainer.step(state, x, y, jax.random.key(step_idx))
            step_idx += 1
            svc.batches_so_far = step_idx
            if step_idx % 10 == 0:
                print(f"epoch {epoch} step {step_idx} loss {float(loss):.4f}")
            if svc.report_loss(float(loss)):
                print("early stop triggered")
                break
            if mgr:
                mgr.maybe_save(step_idx, state.params, {"loss": float(loss)})
    svc.phase = "done"
    print(f"final loss {float(loss):.4f}")
    return 0


def _restore_decode_model(args):
    """Shared restore path for the decode-serving commands (generate /
    serve): checkpoint params + config (npz or orbax backend), with the
    --int8 off|weights|full quantization applied. Returns
    ``(cfg, params)`` or an int exit code on failure."""
    import jax

    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig,
        init_transformer,
        quantize_decode_params,
    )

    from pathlib import Path

    # a read-only command must not mkdir its way past a typo'd path
    # (both managers create their directory tree on construction)
    if not Path(args.checkpoint_dir).is_dir():
        print(f"no checkpoint found in {args.checkpoint_dir}",
              file=sys.stderr)
        return 1
    if args.checkpoint_backend == "orbax":
        from deeplearning4j_tpu.parallel.checkpoint import (
            AsyncShardedCheckpointManager,
        )

        mgr = AsyncShardedCheckpointManager(args.checkpoint_dir)
    else:
        from deeplearning4j_tpu.parallel.checkpoint import CheckpointManager

        mgr = CheckpointManager(args.checkpoint_dir)
    try:
        meta0 = mgr.read_meta()
        if meta0 is None:
            print(
                f"no checkpoint found in {args.checkpoint_dir}",
                file=sys.stderr,
            )
            return 1
        if "config" in meta0:
            # trained config rides in the checkpoint meta — the model
            # flags are not needed (and not trusted) for the template
            cfg = TransformerConfig.from_json(meta0["config"])
        else:
            # pre-config checkpoint: fall back to the model flags, which
            # MUST match the train invocation's (shape errors otherwise)
            cfg = _transformer_cfg_from_args(args)
        if args.int8 != "off" and cfg.n_experts:
            print("--int8 does not cover MoE experts", file=sys.stderr)
            return 2
        cfg = dataclasses.replace(cfg, decode_int8=(args.int8 == "full"))
        template = init_transformer(jax.random.key(0), cfg)
        res = mgr.restore_latest(template)
    finally:
        if hasattr(mgr, "close"):
            mgr.close()
    if res is None:
        print(f"no checkpoint found in {args.checkpoint_dir}", file=sys.stderr)
        return 1
    params, meta = res
    print(f"restored step {meta.get('step')} from {args.checkpoint_dir}")
    if args.int8 != "off":
        params = quantize_decode_params(params, cfg)
        print(f"int8 serving mode: {args.int8} "
              f"({'weights + kv cache' if args.int8 == 'full' else 'weights over a bf16/f32 cache'})")
    return cfg, params


def cmd_generate(args) -> int:
    """Serve a trained transformer checkpoint: restore the params
    (npz or orbax backend), optionally quantize for int8 serving, and
    sample a continuation of --prompt (byte-level, matching train).

    ≙ the reference's sampling entry points (LSTM.java:219 sampleDoc /
    the char-RNN demo) as a standalone serving command; the int8 modes
    are the PERF.md r5 production quantization."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.models.transformer import (
        transformer_beam_search,
        transformer_generate,
    )
    from deeplearning4j_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    restored = _restore_decode_model(args)
    if isinstance(restored, int):
        return restored
    cfg, params = restored

    prompt_bytes = args.prompt.encode("latin-1", errors="replace")
    room = cfg.max_len - len(prompt_bytes)
    if room <= 0:
        print(f"--prompt is {len(prompt_bytes)} bytes; max_len "
              f"({cfg.max_len}) leaves no room to decode", file=sys.stderr)
        return 2
    max_new = min(args.max_new, room)
    prompt = jnp.asarray(
        np.frombuffer(prompt_bytes, np.uint8).astype(np.int32)[None, :]
    )
    if args.beam:
        beam = transformer_beam_search(cfg)
        toks, scores = beam(
            params, prompt, beam_width=args.beam, max_new=max_new
        )
        for w in range(args.beam):
            text = bytes(np.asarray(toks[0, w], np.uint8).tolist())
            print(f"beam {w} (logp {float(scores[0, w]):.2f}):",
                  text.decode("latin-1"))
    else:
        gen = transformer_generate(cfg)
        out = gen(
            params, prompt, jax.random.key(args.seed), max_new,
            temperature=args.temperature,
            top_k=args.top_k if args.top_k > 0 else None,
        )
        text = bytes(np.asarray(out[0], np.uint8).tolist())
        print("sample:", text.decode("latin-1"))
    return 0


def cmd_serve(args) -> int:
    """Run the continuous-batching HTTP serving engine on a trained
    checkpoint (or, with --demo, on a random-init model for smoke
    testing the serving stack without a checkpoint).

    POST /v1/generate {"prompt": "...", "max_new": N} against the
    printed address; GET /metrics for Prometheus text, /metrics.json
    for the summary view. Observability flags: --trace-out (Perfetto
    trace on shutdown), --log-json (structured logs), --metrics-port
    (scrape sidecar), --profile-steps / POST /profile?s=N (XLA
    captures). See the README "Serving"/"Observability" sections."""
    import jax

    from deeplearning4j_tpu.obs import (
        ProfileTrigger,
        Tracer,
        configure_json_logging,
    )
    from deeplearning4j_tpu.serving import (
        FaultInjector,
        RequestScheduler,
        ServingEngine,
        ServingServer,
        TenantRegistry,
    )
    from deeplearning4j_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    if args.log_json:
        configure_json_logging()

    tenancy = None
    if args.tenants:
        tenancy = TenantRegistry.from_file(args.tenants)
        print(f"tenancy: {len(tenancy)} tenants from {args.tenants} "
              f"({', '.join(tenancy.tenant_ids())})")

    if args.demo:
        from deeplearning4j_tpu.models.transformer import init_transformer

        cfg = (_transformer_cfg_from_file(args.model_config)
               if args.model_config else _transformer_cfg_from_args(args))
        params = init_transformer(jax.random.key(0), cfg)
        print(f"demo mode: random-init model ({cfg.d_model}d, "
              f"{cfg.n_layers}L, vocab {cfg.vocab_size})")
    else:
        if not args.checkpoint_dir:
            print("serve needs --checkpoint-dir (or --demo)",
                  file=sys.stderr)
            return 2
        restored = _restore_decode_model(args)
        if isinstance(restored, int):
            return restored
        cfg, params = restored

    lora_bank = None
    if args.lora_adapters > 0:
        from deeplearning4j_tpu.models.transformer import init_lora_bank

        lora_bank = init_lora_bank(
            jax.random.PRNGKey(args.lora_seed), cfg,
            n_adapters=args.lora_adapters, rank=args.lora_rank,
        )
        print(f"batched LoRA: {args.lora_adapters} adapters "
              f"(rank {args.lora_rank}, index 0 = base model); "
              f"requests pick one via 'adapter' or the tenant default")

    embedders = None
    if args.embed_models:
        embedders = _demo_embedders(args.embed_models.split(","))
        print(f"embeddings: POST /v1/embeddings over "
              f"{', '.join(sorted(embedders))} (demo vocab)")

    faults = None
    if args.chaos_rate > 0:
        faults = FaultInjector(
            seed=args.chaos_seed, transient_rate=args.chaos_rate
        )
        print(f"chaos mode: transient faults at rate {args.chaos_rate} "
              f"(seed {args.chaos_seed})")
    tracer = Tracer(
        enabled=args.trace_out is not None,
        capacity=args.trace_capacity,
    )
    profile = ProfileTrigger(log_dir=args.profile_dir)
    if args.profile_steps > 0:
        d = profile.arm(args.profile_steps)
        print(f"profiling first {args.profile_steps} steps -> {d}")
    sans = None
    if args.sanitize:
        from deeplearning4j_tpu.analysis.sanitizers import (
            LockSanitizer,
            SyncSanitizer,
        )

        # install BEFORE the engine/server/router build their locks:
        # wrap_lock only instruments locks created while active
        sans = (LockSanitizer().install(), SyncSanitizer().install())
        print("sanitizers: lock + sync active (development mode)")
    engine = ServingEngine(
        cfg, params,
        n_slots=args.slots,
        max_total=args.max_total,
        temperature=args.temperature,
        top_k=args.top_k if args.top_k > 0 else None,
        decode_horizon=args.decode_horizon,
        adaptive_horizon=args.adaptive_horizon,
        prefix_cache=args.prefix_cache,
        prefix_cache_tokens=args.prefix_cache_tokens,
        paged=args.paged,
        block_size=args.block_size,
        piggyback=args.piggyback,
        prefill_budget=args.prefill_budget,
        sampling_surface=args.sampling_surface,
        grammar_states=args.grammar_states,
        grammar_cache=(
            os.path.expanduser(args.grammar_cache)
            if args.grammar_cache else None
        ),
        scheduler=RequestScheduler(
            max_queue_depth=args.max_queue,
            prefix_affinity_tokens=args.prefix_affinity_tokens,
            tenancy=tenancy,
        ),
        tenancy=tenancy,
        lora_bank=lora_bank,
        embedders=embedders,
        rng_seed=args.seed,
        faults=faults,
        tracer=tracer,
        profile=profile,
        tp=args.tp,
    )
    if sans is not None:
        engine.attach_sanitizer(sans[1])
    if args.paged:
        print(f"paged KV: {engine.pool.n_blocks} blocks x "
              f"{engine.pool.block_size} tokens (shared pool, "
              f"refcounted block tables)")
    if args.piggyback:
        print(f"piggyback prefill: chunked admission fused into "
              f"decode dispatches ({engine.prefill_budget} "
              f"tokens/horizon budget)")
    if args.tp > 1:
        print(f"tensor parallel: decode sharded over {engine.tp} "
              f"devices (model axis)")
    if args.sampling_surface:
        print(f"sampling surface: grammar-constrained decoding + "
              f"per-request temperature/top_k/top_p/stop/"
              f"logit_bias/logprobs "
              f"({engine._gtable.capacity} DFA table rows)")
    server = ServingServer(
        engine, host=args.host, port=args.port,
        request_timeout_s=args.request_timeout,
        max_restarts=args.max_restarts,
        hang_threshold_s=args.hang_threshold,
        metrics_port=args.metrics_port,
        flight_dir=args.flight_dir,
        migrate_targets=tuple(args.migrate_target or ()),
    )
    host, port = server.address
    # name the process track after the bound address so trace-merge
    # shows which replica is which (the port is only known post-bind)
    tracer.process_name = f"serve {host}:{port}"
    print(f"serving on http://{host}:{port}  "
          f"({args.slots} slots, {engine.max_total} tokens/slot, "
          f"decode horizon {engine.decode_horizon}"
          f"{' (adaptive)' if args.adaptive_horizon else ''}, "
          f"queue depth {args.max_queue}, drain {args.drain_s:g}s)")
    if engine.prefix_cache is not None:
        pc = engine.prefix_cache
        print(f"prefix cache: {pc.capacity_tokens} tokens "
              f"({pc.n_region_slots} segments, "
              f"{pc.nbytes() / 1e6:.1f} MB region)")
    if server.metrics_address is not None:
        mh, mp = server.metrics_address
        print(f"metrics sidecar on http://{mh}:{mp}/metrics")
    try:
        if args.run_seconds is not None:
            # timed run (smoke tests / captures): start, optionally
            # publish the bound ports, serve for N seconds, drain
            server.start()
            if args.port_file:
                _write_port_file(args.port_file, server)
            time.sleep(args.run_seconds)
            server.stop(drain_s=args.drain_s)
        else:
            if args.port_file:
                server.start()
                _write_port_file(args.port_file, server)
                try:
                    while True:
                        time.sleep(1)
                except KeyboardInterrupt:
                    pass
                finally:
                    server.stop(args.drain_s)
            else:
                server.serve_forever(drain_s=args.drain_s)
    finally:
        if args.trace_out:
            out = tracer.export(args.trace_out)
            print(f"trace: {tracer.n_events} events "
                  f"({tracer.dropped} dropped) -> {out}")
    if sans is not None:
        return _report_sanitizers(engine, *sans)
    return 0


def _report_sanitizers(engine, lock_san, sync_san) -> int:
    """Uninstall the serve-mode sanitizers, run the compile-count
    guard, print one summary line per detector, and return 1 when any
    violation was recorded. ``engine`` is None for processes that
    never compile programs (the router) — the lock/sync detectors
    still apply, the compile-count guard does not."""
    from deeplearning4j_tpu.analysis.sanitizers import CompileCountGuard

    sync_san.uninstall()
    lock_san.uninstall()
    compile_viol = (
        CompileCountGuard(engine).check() if engine is not None else []
    )
    print(f"sanitizers: {lock_san.n_wrapped} locks tracked, "
          f"sync counts {dict(sorted(sync_san.counts.items()))}")
    violations = (
        [f"[lock] {m}" for m in lock_san.violations]
        + [f"[sync] {m}" for m in sync_san.violations]
        + [f"[compile] {m}" for m in compile_viol]
    )
    for msg in violations:
        print(f"sanitizer violation: {msg}", file=sys.stderr)
    if violations:
        print(f"sanitizers: {len(violations)} violation(s)",
              file=sys.stderr)
        return 1
    print("sanitizers: clean")
    return 0


#: tiny deterministic corpus for --embed-models demo vocabularies
_DEMO_SENTENCES = [
    "the quick brown fox jumps over the lazy dog",
    "a day in the life of a serving engine",
    "music in the park makes the day go by",
    "the fox and the dog share the park",
    "continuous batching keeps the engine busy all day",
]


def _demo_embedders(names: list[str]) -> dict:
    """Zoo embedding models over a tiny fixed corpus for the
    /v1/embeddings demo: word2vec gets random-init vectors (vocab +
    reset_weights, no training), glove a few fast epochs — enough to
    prove the endpoint routes through the serving machinery; real
    deployments would load trained tables."""
    out = {}
    for name in names:
        name = name.strip().lower()
        if not name:
            continue
        if name == "word2vec":
            from deeplearning4j_tpu.models.word2vec import Word2Vec

            m = Word2Vec(layer_size=16, seed=0)
            m.build_vocab(_DEMO_SENTENCES)
            m.reset_weights()
        elif name == "glove":
            from deeplearning4j_tpu.models.glove import Glove

            m = Glove(layer_size=16, epochs=1, seed=0)
            m.fit(_DEMO_SENTENCES)
        else:
            raise ValueError(
                f"unknown embed model {name!r} (word2vec|glove)"
            )
        out[name] = m
    return out


def _write_port_file(path: str, server) -> None:
    """Publish bound addresses for harnesses that passed --port 0."""
    host, port = server.address
    payload = {"host": host, "port": port}
    if server.metrics_address is not None:
        payload["metrics_port"] = server.metrics_address[1]
    tmp = f"{path}.tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(payload, f)
    os.replace(tmp, path)


def cmd_lint(args) -> int:
    """Static analysis for this repo's proven serving bug classes
    (host-sync, zero-copy-alias, prng-reuse, lock-discipline,
    retrace-hazard). Pure stdlib — never imports the linted code.
    Exits 1 on findings not accepted in the baseline
    (.graftlint.json); see README "Correctness tooling"."""
    from deeplearning4j_tpu.analysis import lint as graftlint

    argv = list(args.paths)
    if args.rules:
        argv += ["--rules", args.rules]
    if args.baseline:
        argv += ["--baseline", args.baseline]
    if args.no_baseline:
        argv.append("--no-baseline")
    if args.write_baseline:
        argv.append("--write-baseline")
    if args.strict:
        argv.append("--strict")
    return graftlint.main(argv)


def cmd_audit(args) -> int:
    """jaxpr-level static audit of the serving program surface
    (graftaudit): traces every family the engine can emit as abstract
    avals and checks dtype promotion, donation, collective
    signatures, host callbacks, the compile-surface bounds, and the
    per-family memory/flop budgets in .graftaudit.json. Nothing is
    executed; see README "Correctness tooling"."""
    # the fake-device XLA_FLAGS bootstrap for the TP surface lives in
    # __main__.py: it must run before the package (and with it jax)
    # is imported, which has already happened by the time we get here
    from deeplearning4j_tpu.analysis import audit as graftaudit

    argv = []
    if args.baseline:
        argv += ["--baseline", args.baseline]
    if args.no_baseline:
        argv.append("--no-baseline")
    if args.write_baseline:
        argv.append("--write-baseline")
    if args.strict:
        argv.append("--strict")
    if args.full_budgets:
        argv.append("--full-budgets")
    if args.json_out:
        argv += ["--json-out", args.json_out]
    return graftaudit.main(argv)


def cmd_router(args) -> int:
    """Run the prefix-affinity replica router in front of N running
    `serve` processes. The router never loads a model: it forwards
    POST /v1/generate to the healthy replica with the longest shared
    prompt prefix (least-loaded otherwise), polls each replica's
    /healthz, and retries never-accepted requests when a replica
    dies. See serving/router.py."""
    from deeplearning4j_tpu.obs import Tracer, configure_json_logging
    from deeplearning4j_tpu.serving.router import ReplicaRouter

    if args.log_json:
        configure_json_logging()
    tracer = Tracer(
        enabled=args.trace_out is not None,
        capacity=args.trace_capacity,
        process_name="router",
    )
    sans = None
    if args.sanitize:
        from deeplearning4j_tpu.analysis.sanitizers import (
            LockSanitizer,
            SyncSanitizer,
        )

        # install BEFORE the router builds its locks: wrap_lock only
        # instruments locks created while a sanitizer is active
        sans = (LockSanitizer().install(), SyncSanitizer().install())
        print("sanitizers: lock + sync active (development mode)")
    try:
        router = ReplicaRouter(
            args.replica,
            host=args.host, port=args.port,
            affinity_min_match=args.affinity_min_match,
            health_interval_s=args.health_interval,
            request_timeout_s=args.request_timeout,
            tracer=tracer,
            flight_dir=args.flight_dir,
        )
    except ValueError as e:
        print(f"router: {e}", file=sys.stderr)
        return 2
    host, port = router.address
    tracer.process_name = f"router {host}:{port}"
    names = ", ".join(r.name for r in router.replicas)
    print(f"routing on http://{host}:{port} -> [{names}]  "
          f"(affinity >= {args.affinity_min_match} tokens, "
          f"health poll {args.health_interval:g}s)")
    try:
        if args.port_file:
            router.start()
            tmp = f"{args.port_file}.tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump({"host": host, "port": port}, f)
            os.replace(tmp, args.port_file)
            try:
                while True:
                    time.sleep(1)
            except KeyboardInterrupt:
                pass
            finally:
                router.stop()
        else:
            router.serve_forever()
    finally:
        if args.trace_out:
            out = tracer.export(args.trace_out)
            print(f"trace: {tracer.n_events} events "
                  f"({tracer.dropped} dropped) -> {out}")
    if sans is not None:
        return _report_sanitizers(None, *sans)
    return 0


def cmd_controller(args) -> int:
    """Run the disaggregated-fleet controller in front of N running
    `serve` processes with roles: prompts of --disagg-threshold tokens
    or more prefill on a prefill replica, whose KV segment is pushed
    replica-to-replica to the decode target; everything else (and
    every transfer failure) prefills locally on the decode replica.
    Session-sticky + shadow-affinity routing, hysteretic role
    rebalancing, /fleet/drain rolling restarts. See
    serving/controller.py."""
    from deeplearning4j_tpu.obs import Tracer, configure_json_logging
    from deeplearning4j_tpu.serving.controller import (
        FleetController,
        RoleBalancer,
    )

    if args.log_json:
        configure_json_logging()
    tracer = Tracer(
        enabled=args.trace_out is not None,
        capacity=args.trace_capacity,
        process_name="controller",
    )
    sans = None
    if args.sanitize:
        from deeplearning4j_tpu.analysis.sanitizers import (
            LockSanitizer,
            SyncSanitizer,
        )

        # install BEFORE the controller builds its locks: wrap_lock
        # only instruments locks created while a sanitizer is active
        sans = (LockSanitizer().install(), SyncSanitizer().install())
        print("sanitizers: lock + sync active (development mode)")
    try:
        controller = FleetController(
            args.replica,
            host=args.host, port=args.port,
            disagg_threshold=args.disagg_threshold,
            affinity_min_match=args.affinity_min_match,
            health_interval_s=args.health_interval,
            request_timeout_s=args.request_timeout,
            rebalance=RoleBalancer(
                threshold=args.rebalance_threshold,
                windows=args.rebalance_windows,
                dwell_s=args.rebalance_dwell,
            ),
            rebalance_enabled=not args.no_rebalance,
            hedge_enabled=not args.no_hedge,
            journal=args.journal,
            standby_of=args.standby_of,
            failover_after=args.failover_after,
            tracer=tracer,
            flight_dir=args.flight_dir,
        )
    except ValueError as e:
        print(f"controller: {e}", file=sys.stderr)
        return 2
    host, port = controller.address
    tracer.process_name = f"controller {host}:{port}"
    roles = ", ".join(f"{m.name}={m.role}" for m in controller.members)
    print(f"fleet control on http://{host}:{port} -> [{roles}]  "
          f"(disagg >= {args.disagg_threshold} tokens, "
          f"health poll {args.health_interval:g}s)")
    try:
        if args.port_file:
            controller.start()
            tmp = f"{args.port_file}.tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump({"host": host, "port": port}, f)
            os.replace(tmp, args.port_file)
            try:
                while True:
                    time.sleep(1)
            except KeyboardInterrupt:
                pass
            finally:
                controller.stop()
        else:
            controller.serve_forever()
    finally:
        if args.trace_out:
            out = tracer.export(args.trace_out)
            print(f"trace: {tracer.n_events} events "
                  f"({tracer.dropped} dropped) -> {out}")
    if sans is not None:
        return _report_sanitizers(None, *sans)
    return 0


def cmd_trace_merge(args) -> int:
    """Stitch per-process Chrome-trace exports (each written by a
    serve/router --trace-out) into one Perfetto document: one process
    track per input, timestamps rebased onto a shared wall-clock
    origin, and flow arrows linking router dispatch spans to the
    replica admission spans they parented."""
    from deeplearning4j_tpu.obs.collect import merge_trace_files

    try:
        merged = merge_trace_files(args.traces, out_path=args.out)
    except (OSError, ValueError) as e:
        print(f"trace-merge: {e}", file=sys.stderr)
        return 2
    evs = merged["traceEvents"]
    n_pids = len({e["pid"] for e in evs})
    n_spans = sum(1 for e in evs if e.get("ph") == "X")
    n_flows = sum(1 for e in evs if e.get("ph") == "s")
    print(f"merged {len(args.traces)} traces -> {args.out}: "
          f"{n_pids} process tracks, {n_spans} spans, "
          f"{n_flows} cross-process links "
          f"(open at https://ui.perfetto.dev)")
    return 0


def cmd_bench(args) -> int:
    import bench

    # bench has its own argparse; forward only the args meant for it
    # (sys.argv still holds this CLI's "bench" subcommand)
    bench.main(list(getattr(args, "bench_args", []) or []))
    return 0


def cmd_status(args) -> int:
    import urllib.request

    with urllib.request.urlopen(f"http://127.0.0.1:{args.port}/statetracker") as r:
        print(json.dumps(json.loads(r.read()), indent=2))
    return 0


def cmd_provision(args) -> int:
    """Render or EXECUTE cluster provisioning (≙ ClusterSetup.java:24,
    which actually SSHes; default here is the safe dry run — every
    command that would execute is printed; --execute runs them)."""
    from deeplearning4j_tpu.utils.provision import (
        ClusterSetup,
        ClusterSpec,
        RecordingRunner,
        SubprocessRunner,
    )

    spec = ClusterSpec(
        name=args.name,
        num_workers=args.num_workers,
        accelerator_type=args.accelerator_type,
        zone=args.zone,
        master_script=args.master_script,
        worker_script=args.worker_script,
    )
    runner = SubprocessRunner() if args.execute else RecordingRunner()
    setup = ClusterSetup(spec, runner=runner)
    try:
        names = setup.provision()
    except Exception as e:  # ProvisionError / subprocess timeouts
        print(f"provisioning failed: {e}", file=sys.stderr)
        return 1
    if not args.execute:
        import shlex

        for cmd in runner.commands:
            print(shlex.join(cmd))  # paste-safe: spaced args stay quoted
        print(f"# dry run: {len(runner.commands)} commands for "
              f"{', '.join(names)} (pass --execute to run)")
    else:
        print(f"provisioned: {', '.join(names)}")
    return 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="deeplearning4j_tpu")
    sub = parser.add_subparsers(dest="cmd", required=True)

    t = sub.add_parser("train", help="train a model (single or multi-host SPMD)")
    t.add_argument(
        "--model", default="lenet",
        choices=["lenet", "alexnet", "transformer"],
    )
    t.add_argument("--epochs", type=int, default=1)
    t.add_argument("--batch", type=int, default=256)
    t.add_argument("--examples", type=int, default=4096)
    t.add_argument("--checkpoint-dir", default=None)
    t.add_argument(
        "--checkpoint-backend", default="npz", choices=["npz", "orbax"],
        help="orbax = async shard-local writes (transformer only)",
    )
    t.add_argument("--save-every", type=int, default=50)
    t.add_argument("--status-port", type=int, default=None)
    t.add_argument(
        "--status-host", default="127.0.0.1",
        help="interface for the status REST server (default loopback; "
        "multi-host deployments pass 0.0.0.0 or a routable address so "
        "remote workers reach the heartbeat/control endpoints)",
    )
    t.add_argument(
        "--status-token", default=None,
        help="shared secret for control POSTs (X-Auth-Token header); "
        "auto-generated and logged when binding non-loopback without one",
    )
    # transformer-only knobs
    t.add_argument("--text", default=None, help="path to a byte-level corpus")
    t.add_argument("--steps", type=int, default=200)
    t.add_argument("--seq-len", type=int, default=128)
    t.add_argument("--d-model", type=int, default=128)
    t.add_argument("--n-layers", type=int, default=2)
    t.add_argument("--n-heads", type=int, default=4)
    t.add_argument("--n-experts", type=int, default=0)
    t.add_argument("--tp", type=int, default=1, help="tensor-parallel ways")
    t.add_argument("--fsdp", action="store_true")
    t.add_argument(
        "--flash", action="store_true",
        help="pallas flash attention (seq-len a multiple of 8, and "
        "<= 128 or a multiple of 128); the TPU perf recipe — see PERF.md",
    )
    t.add_argument(
        "--remat", action="store_true",
        help="selective rematerialization (dots_no_batch policy): "
        "recompute elementwise ops in backward instead of storing the "
        "(B,H,T,T) attention probs — required for long-context training",
    )
    t.add_argument(
        "--bf16", action="store_true",
        help="bfloat16 compute (f32 params/softmax) — MXU-native",
    )
    _add_distributed_flags(t)
    t.set_defaults(fn=cmd_train)

    g = sub.add_parser(
        "generate",
        help="sample from a trained transformer checkpoint "
        "(byte-level; --int8 weights|full for quantized serving)",
    )
    g.add_argument("--checkpoint-dir", required=True)
    g.add_argument(
        "--checkpoint-backend", default="npz", choices=["npz", "orbax"],
    )
    g.add_argument("--prompt", default="the quick brown ")
    g.add_argument("--max-new", type=int, default=48)
    g.add_argument("--temperature", type=float, default=0.8)
    g.add_argument("--top-k", type=int, default=40,
                   help="0 disables top-k filtering")
    g.add_argument("--beam", type=int, default=0,
                   help="beam width; 0 = sampled decode")
    g.add_argument("--seed", type=int, default=0)
    g.add_argument(
        "--int8", default="off", choices=["off", "weights", "full"],
        help="weight-only int8 (over a float cache) or the fully "
        "quantized path (int8 KV cache too) — PERF.md r5",
    )
    # model flags: fallback ONLY for checkpoints saved before the config
    # rode in the meta — then they must match the train invocation
    g.add_argument("--seq-len", type=int, default=128)
    g.add_argument("--d-model", type=int, default=128)
    g.add_argument("--n-layers", type=int, default=2)
    g.add_argument("--n-heads", type=int, default=4)
    g.add_argument("--n-experts", type=int, default=0)
    g.add_argument("--bf16", action="store_true")
    g.set_defaults(fn=cmd_generate)

    v = sub.add_parser(
        "serve",
        help="continuous-batching HTTP serving engine over a trained "
        "checkpoint (POST /v1/generate; --demo for a random-init model)",
    )
    v.add_argument("--checkpoint-dir", default=None)
    v.add_argument(
        "--checkpoint-backend", default="npz", choices=["npz", "orbax"],
    )
    v.add_argument("--demo", action="store_true",
                   help="serve a random-init model (no checkpoint)")
    v.add_argument("--host", default="127.0.0.1")
    v.add_argument("--port", type=int, default=8080)
    v.add_argument("--slots", type=int, default=8,
                   help="decode slots = max concurrent requests in flight")
    v.add_argument("--max-total", type=int, default=None,
                   help="token budget per slot (prompt+generation; "
                   "default: the model's max_len)")
    v.add_argument("--max-queue", type=int, default=128,
                   help="queued requests beyond which submits get 429")
    v.add_argument("--temperature", type=float, default=0.8)
    v.add_argument("--top-k", type=int, default=40,
                   help="0 disables top-k filtering")
    v.add_argument("--seed", type=int, default=0)
    v.add_argument("--request-timeout", type=float, default=300.0,
                   help="seconds a handler waits before answering 504 "
                   "(the request is cancelled in the engine, freeing "
                   "its KV slot)")
    v.add_argument("--decode-horizon", type=int, default=4,
                   help="decode steps fused into one dispatched device "
                   "program (K); tokens are read back one horizon "
                   "behind dispatch, amortizing launch + host-sync "
                   "overhead at the cost of up-to-K-steps extra "
                   "admission/first-token latency. 1 = per-step "
                   "cadence. bench serve sweeps K and reports the "
                   "winning horizon")
    v.add_argument("--adaptive-horizon", action="store_true",
                   help="shrink the decode horizon to 1 while requests "
                   "wait in the queue (admissions happen at horizon "
                   "boundaries) and restore --decode-horizon when it "
                   "drains; token streams are unchanged")
    v.add_argument("--prefix-cache", action="store_true",
                   help="radix-tree KV prefix cache: admissions whose "
                   "prompt shares a cached prefix copy those KV rows "
                   "instead of recomputing them. "
                   "Hit rate and saved prefill tokens appear in "
                   "/metrics")
    v.add_argument("--prefix-cache-tokens", type=int, default=None,
                   metavar="N",
                   help="device-side prefix-cache capacity in tokens "
                   "(default: slots x tokens-per-slot, i.e. a region "
                   "as large as the slot pool)")
    v.add_argument("--paged", action="store_true",
                   help="block-paged KV: slots hold int32 block tables "
                   "over one shared refcounted pool instead of fixed "
                   "slabs — prefix-cache hits alias blocks (zero-copy) "
                   "and long-context mixes fit more concurrent slots "
                   "at the same HBM")
    v.add_argument("--block-size", type=int, default=None, metavar="T",
                   help="tokens per KV block with --paged (default: "
                   "8; one that does not divide tokens-per-slot is an "
                   "error at start)")
    v.add_argument("--piggyback", action="store_true",
                   help="chunked-prefill piggyback: long prompts are "
                   "split into pow2 chunks and ride along with decode "
                   "dispatches (one fused program per horizon) instead "
                   "of stalling active streams behind a blocking "
                   "prefill. Token-budgeted per horizon; byte-identical "
                   "streams")
    v.add_argument("--prefill-budget", type=int, default=None,
                   metavar="N",
                   help="piggyback prefill token budget per decode "
                   "horizon (default: 2x the largest prefill bucket)")
    v.add_argument("--sampling-surface", action="store_true",
                   help="enable the production sampling surface: "
                   "grammar-constrained decoding (response_format with "
                   "a JSON schema or regex), per-request temperature/"
                   "top_k/top_p overrides, stop sequences, logit_bias "
                   "and logprobs. One masked program family serves "
                   "every request mix; unconstrained streams stay "
                   "byte-identical")
    v.add_argument("--grammar-states", type=int, default=256,
                   metavar="N",
                   help="device DFA table rows shared by all seated "
                   "grammars (default: 256); compiles whose DFA "
                   "exceeds the free budget are rejected with 400")
    v.add_argument("--grammar-cache", type=str, default=None,
                   metavar="DIR",
                   help="on-disk grammar compile cache directory "
                   "(default: in-memory LRU only)")
    v.add_argument("--prefix-affinity-tokens", type=int, default=0,
                   metavar="K",
                   help="scheduler promotes a queued request whose "
                   "first K prompt tokens match the previous admission "
                   "(same priority class only), so shared-prefix "
                   "requests land in the same admission batch; 0 = "
                   "plain FIFO")
    v.add_argument("--drain-s", type=float, default=5.0,
                   help="graceful-drain window on shutdown: admission "
                   "stops (503) and in-flight requests get this many "
                   "seconds to finish; stragglers still decoding at "
                   "the deadline are preempted (cancelled, partial "
                   "stream returned with HTTP 499)")
    v.add_argument("--hang-threshold", type=float, default=120.0,
                   help="seconds without an engine-loop heartbeat "
                   "(while work is pending) before /healthz reports "
                   "the engine hung and flips to 503")
    v.add_argument("--max-restarts", type=int, default=5,
                   help="consecutive engine-crash recoveries before "
                   "the server declares the engine dead (/healthz 503)")
    v.add_argument("--migrate-target", action="append", default=None,
                   metavar="HOST:PORT",
                   help="peer replica eligible to re-seat this "
                   "replica's in-flight sessions on drain (POST "
                   "/migrate also accepts explicit targets); repeat "
                   "per peer")
    v.add_argument("--chaos-rate", type=float, default=0.0,
                   help="inject transient faults at engine boundaries "
                   "at this per-step probability (smoke-tests the "
                   "supervised retry/replay path; see serving/faults.py)")
    v.add_argument("--chaos-seed", type=int, default=0)
    v.add_argument("--sanitize", action="store_true",
                   help="development mode: enable the runtime "
                   "sanitizers (lock-order + lockset tracking, "
                   "per-phase blocking-sync budgets, dispatch-alias "
                   "integrity, compile-count bounds) and exit nonzero "
                   "if any fires; see README 'Correctness tooling'")
    v.add_argument("--trace-out", default=None, metavar="PATH",
                   help="enable the request-lifecycle tracer and write "
                   "a Chrome-trace/Perfetto JSON of the ring-buffered "
                   "spans to PATH on shutdown (open at "
                   "https://ui.perfetto.dev)")
    v.add_argument("--trace-capacity", type=int, default=1 << 16,
                   help="tracer ring-buffer size in events (oldest "
                   "overwritten beyond this)")
    v.add_argument("--flight-dir", default=None, metavar="DIR",
                   help="write crash flight-recorder bundles (JSON "
                   "postmortems: recent engine events, metrics, trace "
                   "tail — prompts redacted) to DIR on engine crash, "
                   "watchdog trip, or SIGTERM; also honours "
                   "DL4J_TPU_FLIGHT_DIR. GET /debug/dump serves the "
                   "live bundle regardless")
    v.add_argument("--log-json", action="store_true",
                   help="structured JSON logs (one object per line on "
                   "stderr) with req_id correlation across scheduler/"
                   "engine/server events")
    v.add_argument("--metrics-port", type=int, default=None,
                   help="serve Prometheus /metrics (+ /healthz) on a "
                   "dedicated sidecar port, isolated from generate "
                   "traffic on the main port")
    v.add_argument("--profile-dir", default="/tmp/dl4j_tpu_profile",
                   help="directory XLA profiler captures land in "
                   "(armed via POST /profile?s=N or --profile-steps)")
    v.add_argument("--profile-steps", type=int, default=0,
                   help="arm an XLA profiler capture of the FIRST N "
                   "engine steps at startup (0 = only on-demand via "
                   "POST /profile)")
    v.add_argument("--run-seconds", type=float, default=None,
                   help="run for N seconds then drain and exit "
                   "(smoke tests / timed captures; default: serve "
                   "until Ctrl-C)")
    v.add_argument("--port-file", default=None, metavar="PATH",
                   help="write the bound addresses as JSON to PATH "
                   "once listening (for harnesses using --port 0)")
    v.add_argument(
        "--int8", default="off", choices=["off", "weights", "full"],
        help="weight-only int8 or the fully quantized path (int8 KV "
        "cache) — PERF.md r5",
    )
    v.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel width: shard the fused decode "
                   "program (attention heads, MLP columns, vocab) and "
                   "the KV slot pool over the first N devices. Needs "
                   "N devices and N dividing n_heads and kv_heads, or "
                   "the server does not start. 1 = single device")
    v.add_argument("--tenants", default=None, metavar="PATH",
                   help="JSON tenant registry enabling multi-tenant "
                   "serving: API-key resolution (X-API-Key / Bearer), "
                   "per-tenant priority + weighted-fair share, KV-slot "
                   "caps, token-rate quotas (429), and a default LoRA "
                   "adapter per tenant. See README 'Multi-tenant "
                   "serving' for the schema")
    v.add_argument("--lora-adapters", type=int, default=0, metavar="N",
                   help="load a batched-LoRA bank of N adapters "
                   "(random-init demo factors; index 0 is the zero "
                   "adapter = bitwise base model) so one engine serves "
                   "N fine-tunes in one decode batch; requests select "
                   "one via 'adapter' or the tenant's default_adapter. "
                   "0 = no bank")
    v.add_argument("--lora-rank", type=int, default=4,
                   help="low-rank dimension of the demo LoRA factors")
    v.add_argument("--lora-seed", type=int, default=0,
                   help="PRNG seed for the demo LoRA bank")
    v.add_argument("--embed-models", default=None, metavar="M[,M]",
                   help="comma-separated zoo embedding models "
                   "(word2vec, glove) to serve at POST /v1/embeddings "
                   "over a small demo vocabulary")
    v.add_argument("--model-config", default=None, metavar="FILE",
                   help="with --demo: the model as a JSON file of "
                   "TransformerConfig fields, or a benchmark configuration "
                   "file whose 'model' group holds them (e.g. "
                   "benchmark/configs/laguna-s-2.1.json), instead of the "
                   "model flags below")
    # model flags for --demo / pre-config checkpoints
    v.add_argument("--seq-len", type=int, default=128)
    v.add_argument("--d-model", type=int, default=128)
    v.add_argument("--n-layers", type=int, default=2)
    v.add_argument("--n-heads", type=int, default=4)
    v.add_argument("--n-experts", type=int, default=0)
    v.add_argument("--bf16", action="store_true")
    v.set_defaults(fn=cmd_serve)

    r = sub.add_parser(
        "router",
        help="prefix-affinity router over N running serve replicas "
        "(least-loaded dispatch, per-replica health, crash retry)",
    )
    r.add_argument("--replica", action="append", required=True,
                   metavar="HOST:PORT",
                   help="one backend serve address; repeat per replica")
    r.add_argument("--host", default="127.0.0.1")
    r.add_argument("--port", type=int, default=8000)
    r.add_argument("--affinity-min-match", type=int, default=8,
                   help="shared-prefix tokens before affinity overrides "
                   "least-loaded dispatch (route to the replica whose "
                   "prefix cache likely holds the matching KV)")
    r.add_argument("--health-interval", type=float, default=0.5,
                   help="seconds between /healthz polls of each replica")
    r.add_argument("--request-timeout", type=float, default=300.0)
    r.add_argument("--trace-out", default=None, metavar="PATH",
                   help="enable the router's dispatch tracer and write "
                   "its Chrome-trace/Perfetto JSON to PATH on shutdown "
                   "(merge with replica traces via trace-merge)")
    r.add_argument("--trace-capacity", type=int, default=1 << 16,
                   help="tracer ring-buffer size in events")
    r.add_argument("--flight-dir", default=None, metavar="DIR",
                   help="write the router's flight-recorder bundle to "
                   "DIR on SIGTERM; also honours DL4J_TPU_FLIGHT_DIR. "
                   "GET /debug/dump serves the live bundle regardless")
    r.add_argument("--log-json", action="store_true")
    r.add_argument("--port-file", default=None, metavar="PATH",
                   help="write the bound address as JSON to PATH once "
                   "listening (for harnesses using --port 0)")
    r.add_argument("--sanitize", action="store_true",
                   help="development mode: enable the runtime "
                   "sanitizers (lock-order + lockset tracking, "
                   "blocking-sync budgets) on the router's own "
                   "threads and exit nonzero at shutdown if any "
                   "violation was recorded")
    r.set_defaults(fn=cmd_router)

    c = sub.add_parser(
        "controller",
        help="disaggregated-fleet controller over N serve replicas "
        "with prefill/decode roles (KV-segment transfer for long "
        "prompts, session stickiness, hysteretic role rebalancing, "
        "rolling-restart draining)",
    )
    c.add_argument("--replica", action="append", required=True,
                   metavar="HOST:PORT[=ROLE]",
                   help="one backend serve address with an optional "
                   "role (prefill|decode|monolithic, default "
                   "monolithic); repeat per replica")
    c.add_argument("--host", default="127.0.0.1")
    c.add_argument("--port", type=int, default=8000)
    c.add_argument("--disagg-threshold", type=int, default=64,
                   metavar="N",
                   help="prompt length (tokens) at which a request "
                   "takes the prefill->transfer->decode path; below "
                   "it the wire transfer costs more than the prefill "
                   "it moves (see PERF.md for the heuristic)")
    c.add_argument("--affinity-min-match", type=int, default=8,
                   help="shared-prefix tokens before shadow affinity "
                   "overrides least-loaded decode dispatch")
    c.add_argument("--health-interval", type=float, default=0.5,
                   help="seconds between health/SLO polls of each "
                   "replica (also the rebalance sampling cadence)")
    c.add_argument("--request-timeout", type=float, default=300.0)
    c.add_argument("--rebalance-threshold", type=float, default=2.0,
                   help="pressure ratio (queue depth + SLO burn) one "
                   "role pool must exceed over the other before a "
                   "role flip is considered")
    c.add_argument("--rebalance-windows", type=int, default=3,
                   help="consecutive imbalanced samples required "
                   "before flipping a role (hysteresis)")
    c.add_argument("--rebalance-dwell", type=float, default=30.0,
                   help="minimum seconds between role flips")
    c.add_argument("--no-rebalance", action="store_true",
                   help="disable automatic role rebalancing (roles "
                   "still movable via POST /fleet/role)")
    c.add_argument("--no-hedge", action="store_true",
                   help="disable hedged second attempts on the "
                   "idempotent KV-transfer leg (generate legs are "
                   "never hedged)")
    c.add_argument("--journal", default=None, metavar="PATH",
                   help="journal roles/stickiness/breaker state to "
                   "PATH (atomic rewrite) so a warm standby can take "
                   "over after a controller crash")
    c.add_argument("--standby-of", default=None, metavar="HOST:PORT",
                   help="run as a warm standby: answer 503 to all "
                   "traffic while watching the primary controller at "
                   "HOST:PORT; promote from --journal after "
                   "--failover-after consecutive missed health checks")
    c.add_argument("--failover-after", type=int, default=3,
                   help="consecutive missed primary health checks "
                   "before a standby promotes itself")
    c.add_argument("--trace-out", default=None, metavar="PATH",
                   help="enable the controller's dispatch tracer and "
                   "write its Chrome-trace/Perfetto JSON to PATH on "
                   "shutdown (merge with replica traces via "
                   "trace-merge)")
    c.add_argument("--trace-capacity", type=int, default=1 << 16,
                   help="tracer ring-buffer size in events")
    c.add_argument("--flight-dir", default=None, metavar="DIR",
                   help="write the controller's flight-recorder "
                   "bundle to DIR on SIGTERM; also honours "
                   "DL4J_TPU_FLIGHT_DIR. GET /debug/dump serves the "
                   "live bundle regardless")
    c.add_argument("--log-json", action="store_true")
    c.add_argument("--port-file", default=None, metavar="PATH",
                   help="write the bound address as JSON to PATH once "
                   "listening (for harnesses using --port 0)")
    c.add_argument("--sanitize", action="store_true",
                   help="development mode: runtime sanitizers on the "
                   "controller's own threads; exit nonzero at "
                   "shutdown if any violation was recorded")
    c.set_defaults(fn=cmd_controller)

    m = sub.add_parser(
        "trace-merge",
        help="stitch per-process --trace-out exports (router + "
        "replicas) into one Perfetto trace with cross-process flow "
        "arrows from router dispatch spans to replica admissions",
    )
    m.add_argument("traces", nargs="+", metavar="TRACE.json",
                   help="per-process Chrome-trace JSON files")
    m.add_argument("-o", "--out", required=True, metavar="PATH",
                   help="merged Perfetto JSON output path")
    m.set_defaults(fn=cmd_trace_merge)

    L = sub.add_parser(
        "lint",
        help="static analysis for the serving stack's proven bug "
        "classes (graftlint); exits 1 on non-baselined findings",
    )
    L.add_argument("paths", nargs="*",
                   help="files/dirs to lint (default: the installed "
                   "deeplearning4j_tpu package)")
    L.add_argument("--rules", default=None, metavar="R1,R2",
                   help="comma-separated rule subset")
    L.add_argument("--baseline", default=None, metavar="PATH",
                   help="baseline JSON (default: .graftlint.json at "
                   "the repo root)")
    L.add_argument("--no-baseline", action="store_true",
                   help="report every finding, ignoring the baseline")
    L.add_argument("--write-baseline", action="store_true",
                   help="accept the current findings into the baseline")
    L.add_argument("--strict", action="store_true",
                   help="also fail on stale baseline entries and TODO "
                   "reasons (CI mode)")
    L.set_defaults(fn=cmd_lint)

    A = sub.add_parser(
        "audit",
        help="statically audit every compiled program family the "
        "serving engine can emit (graftaudit: jaxpr dtype/donation/"
        "collective/callback/surface checks + memory/flop budgets); "
        "exits 1 on findings",
    )
    A.add_argument("--baseline", default=None, metavar="PATH",
                   help="budget baseline JSON (default: "
                   ".graftaudit.json at the repo root)")
    A.add_argument("--no-baseline", action="store_true",
                   help="skip baseline comparison entirely")
    A.add_argument("--write-baseline", action="store_true",
                   help="(re)write the baseline from this run")
    A.add_argument("--strict", action="store_true",
                   help="also fail on stale baseline entries (CI mode)")
    A.add_argument("--full-budgets", action="store_true",
                   help="compile every program for budgets, not just "
                   "each family's envelope")
    A.add_argument("--json-out", default=None, metavar="PATH",
                   help="write the full report as JSON (CI artifact)")
    A.set_defaults(fn=cmd_audit)

    # add_help=False so `bench -h` reaches bench.py's parser, which
    # documents --model/--batch/--dtype
    b = sub.add_parser("bench", add_help=False,
                       help="run the benchmark harness "
                       "(flags are forwarded to bench.py, "
                       "e.g. --model alexnet)")
    b.set_defaults(fn=cmd_bench)

    s = sub.add_parser("status", help="query a running trainer's REST status")
    s.add_argument("--port", type=int, required=True)
    s.set_defaults(fn=cmd_status)

    p = sub.add_parser(
        "provision",
        help="provision a TPU-VM cluster (dry run by default; "
        "--execute runs the gcloud/ssh commands)",
    )
    p.add_argument("name")
    p.add_argument("--accelerator-type", default="v5litepod-8")
    p.add_argument("--zone", default="us-central1-a")
    p.add_argument("--num-workers", type=int, default=0,
                   help="worker VMs besides the master")
    p.add_argument("--master-script", default=None,
                   help="setup script run on the master after create")
    p.add_argument("--worker-script", default=None,
                   help="setup script run on each worker after create")
    p.add_argument("--execute", action="store_true",
                   help="actually run the commands (default: print them)")
    p.set_defaults(fn=cmd_provision)

    effective = argv if argv is not None else sys.argv[1:]
    if effective[:1] == ["bench"]:
        # bench owns its flags (--model/--batch/--dtype): parse only the
        # subcommand here and forward the rest verbatim
        args, extra = parser.parse_known_args(argv)
        args.bench_args = extra
    else:
        args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
