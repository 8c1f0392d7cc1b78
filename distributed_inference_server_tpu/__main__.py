"""CLI entry point: ``python -m distributed_inference_server_tpu``.

The reference's binary entry (``src/main.rs``, placeholder; startup flow
``tasks.md:298-312`` [spec], SURVEY.md §3.1): load config (CLI > env >
file, exiting non-zero on invalid values — Property 27), build the engine
fleet, serve HTTP until interrupted.
"""

from __future__ import annotations

import asyncio
import sys


def main(argv=None) -> int:
    from distributed_inference_server_tpu.core.errors import (
        ConfigError,
        ModelLoadError,
    )
    from distributed_inference_server_tpu.serving.config import (
        ConfigWatcher,
        ServerConfig,
    )

    try:
        cfg = ServerConfig.load(cli_args=sys.argv[1:] if argv is None else argv)
    except ConfigError as e:
        print(f"config error: {e}", file=sys.stderr)
        return 2

    # fault injection (docs/RESILIENCE.md): armed only when faults.spec
    # is set (config file, DIS_TPU_FAULTS__SPEC env, or --faults-spec) —
    # chaos/soak tooling only, never production
    faults_spec = cfg.get("faults", "spec")
    if faults_spec:
        from distributed_inference_server_tpu.serving import faults

        faults.install(faults.parse_spec(faults_spec,
                                         cfg.get("faults", "seed")))

    # multi-host data plane: connect to the fleet BEFORE any backend
    # touches devices (parallel/distributed.py; SURVEY §5 two-plane design)
    nproc = cfg.get("distributed", "num_processes")
    if nproc > 1:
        from distributed_inference_server_tpu.parallel.distributed import (
            DistributedConfig,
            initialize,
        )

        initialize(DistributedConfig(
            coordinator_address=cfg.get("distributed", "coordinator_address"),
            num_processes=nproc,
            process_id=cfg.get("distributed", "process_id"),
        ))

    import jax
    import jax.numpy as jnp

    from distributed_inference_server_tpu.utils.compile_cache import (
        setup_compile_cache,
    )

    cache_dir = setup_compile_cache()
    # jax falls back to the CPU with one quiet warning when it finds no
    # accelerator; a server that then answers at CPU speed looks healthy
    # to every probe. Serve on the CPU only when it was asked for.
    try:
        dev0 = jax.devices()[0]
    except RuntimeError as e:  # the requested platform has no device here
        print(f"startup error: {e}", file=sys.stderr)
        return 1
    if dev0.platform == "cpu" and (jax.config.jax_platforms or "") != "cpu":
        print(
            "startup error: jax found no accelerator and fell back to the "
            f"CPU backend (JAX_PLATFORMS={jax.config.jax_platforms!r}); "
            "set JAX_PLATFORMS=cpu to serve on the CPU deliberately",
            file=sys.stderr,
        )
        return 1
    print(f"backend: {dev0.platform} ({dev0.device_kind}) x "
          f"{len(jax.devices())}; compile cache: {cache_dir}")

    from distributed_inference_server_tpu.engine.engine import (
        EngineConfig,
        LLMEngine,
    )
    from distributed_inference_server_tpu.engine.kv_cache import PagedCacheConfig
    from distributed_inference_server_tpu.models import llama
    from distributed_inference_server_tpu.models.configs import get_config
    from distributed_inference_server_tpu.models.loader import load_checkpoint
    from distributed_inference_server_tpu.models.tokenizer import load_tokenizer
    from distributed_inference_server_tpu.serving.server import InferenceServer

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
             "float16": jnp.float16}[cfg.get("model", "dtype")]
    model_dir = cfg.get("model", "model_dir") or None
    engine_cfg = EngineConfig(
        max_batch=cfg.get("engine", "max_batch"),
        prefill_buckets=tuple(cfg.get("engine", "prefill_buckets")),
        paged=PagedCacheConfig(
            num_pages=cfg.get("engine", "num_pages"),
            page_size=cfg.get("engine", "page_size"),
            max_pages_per_seq=cfg.get("engine", "max_pages_per_seq"),
        ),
        decode_block_size=cfg.get("engine", "decode_block_size"),
        pipeline_depth=cfg.get("engine", "pipeline_depth"),
        prefill_batch=cfg.get("engine", "prefill_batch"),
        prefill_token_budget=cfg.get("engine", "prefill_token_budget"),
        # ragged mixed-batch stepping (docs/PERF.md): one dispatch for
        # decode rows + prefill chunks while prefill work is pending
        mixed_step_tokens=cfg.get("engine", "mixed_step_tokens"),
        # run-to-completion looped decode blocks (docs/PERF.md "Kernel
        # Looping"): one dispatch runs to the stop condition on-device
        loop_to_completion=cfg.get("engine", "loop_to_completion"),
        loop_max_steps=cfg.get("engine", "loop_max_steps"),
        pp_microbatches=cfg.get("engine", "pp_microbatches"),
        cp_min_tokens=cfg.get("engine", "cp_min_tokens") or None,
        sp_impl=cfg.get("engine", "sp_impl"),
        warmup_compile=cfg.get("engine", "warmup_compile"),
        kv_quant=cfg.get("engine", "kv_quant"),
        # tiered prefix cache (docs/CACHING.md): host-RAM demotion pool
        host_tier_bytes=cfg.get("cache", "host_tier_bytes"),
        host_tier_quant=cfg.get("cache", "host_tier_quant"),
        # latent page codec (docs/CACHING.md "Latent KV pages"): rank-r
        # projection for latent/latent_int8 wire + tier encodings
        latent_rank=cfg.get("cache", "latent_rank"),
        # fleet prefix sharing: routing-digest chain depth
        digest_depth=cfg.get("cache", "digest_depth"),
    )
    tokenizer = load_tokenizer(model_dir)

    # Clamp the validator's context limit to what the engine can actually
    # seat (page_size * max_pages_per_seq - 1 for the sampled token), so
    # over-long prompts are 400s at the validator, not 500s at the engine.
    engine_prompt_cap = (
        cfg.get("engine", "page_size") * cfg.get("engine", "max_pages_per_seq") - 1
    )
    validator_cfg = cfg.validator_config()
    if validator_cfg.max_context_tokens > engine_prompt_cap:
        from dataclasses import replace as _replace

        validator_cfg = _replace(
            validator_cfg, max_context_tokens=engine_prompt_cap
        )

    tp = cfg.get("engine", "tensor_parallel")
    pp = cfg.get("engine", "pipeline_parallel")
    cp = cfg.get("engine", "context_parallel")
    per_replica = tp * pp * cp
    num_engines = cfg.get("server", "num_engines")
    # combinations the engine rejects must fail here as a config error
    # (Property 27: exit non-zero on invalid config), not per-replica at
    # construction time with every engine marked unhealthy
    if cp > 1 and pp > 1:
        print(
            "config error: engine.context_parallel > 1 with "
            "engine.pipeline_parallel > 1 is not supported",
            file=sys.stderr,
        )
        return 2
    has_draft = bool(cfg.get("model", "draft_model_dir")
                     or cfg.get("model", "draft_model_name"))
    if has_draft and pp > 1:
        print(
            "config error: speculative decoding (model.draft_model_*) "
            "with engine.pipeline_parallel > 1 is not supported",
            file=sys.stderr,
        )
        return 2
    # Under the multi-host runtime each HOST serves its own replicas on
    # its own chips (the two-plane design: the router is the cross-host
    # control plane, serving/router.py) — meshes must be built from
    # LOCAL devices, never global slices (a single logical engine
    # spanning hosts requires every host to run the same SPMD program,
    # which an independent per-host request stream cannot guarantee).
    def _devices():
        return jax.local_devices() if nproc > 1 else jax.devices()

    n_avail = len(_devices())
    if per_replica > 1:
        needed = per_replica * num_engines
        if needed > n_avail:
            print(
                f"config error: {num_engines} engines x (tensor_parallel="
                f"{tp} x pipeline_parallel={pp} x context_parallel={cp}) "
                f"needs {needed} devices, have {n_avail}"
                + (" on this host" if nproc > 1 else ""),
                file=sys.stderr,
            )
            return 2

    def engine_factory(replica_idx: int) -> LLMEngine:
        device = None
        if per_replica == 1:
            # one-chip replicas spread over the host's chips (wrapping
            # when there are more replicas than chips): replica i is
            # pinned to device i, or every replica's weights and pool
            # would land on device 0
            device = _devices()[replica_idx % n_avail]
        # build on the replica's own device so nothing transits device 0
        with jax.default_device(device):
            return _build_engine(replica_idx, device)

    def _build_engine(replica_idx: int, device) -> LLMEngine:
        if model_dir:
            params, model_cfg = load_checkpoint(model_dir, dtype=dtype)
        else:
            model_cfg = get_config(cfg.get("model", "model_name"))
            params = llama.init_params(jax.random.PRNGKey(0), model_cfg,
                                       dtype=dtype)
        quant = cfg.get("model", "quantization")
        if quant != "none":
            from distributed_inference_server_tpu.ops.quant import (
                quantize_params,
            )

            params = quantize_params(params, quant)
        mesh = None
        if per_replica > 1:
            from distributed_inference_server_tpu.parallel import (
                MeshSpec,
                make_mesh,
            )

            # each replica gets a DISJOINT slice of THIS HOST's devices:
            # replica i owns devices [i*per_replica, (i+1)*per_replica)
            devs = _devices()[
                replica_idx * per_replica : (replica_idx + 1) * per_replica
            ]
            mesh = make_mesh(MeshSpec(tensor=tp, stage=pp, seq=cp), devs)
        # speculative decoding (Req 12.1): a draft model configured on the
        # server enables speculation inside the continuous-batching engine
        draft_params = draft_cfg_m = spec = None
        draft_dir = cfg.get("model", "draft_model_dir") or None
        draft_name = cfg.get("model", "draft_model_name") or None
        if draft_dir or draft_name:
            from distributed_inference_server_tpu.engine.speculative import (
                SpecConfig,
            )

            if draft_dir:
                draft_params, draft_cfg_m = load_checkpoint(
                    draft_dir, dtype=dtype
                )
            else:
                draft_cfg_m = get_config(draft_name)
                draft_params = llama.init_params(
                    jax.random.PRNGKey(1), draft_cfg_m, dtype=dtype
                )
            spec = SpecConfig(
                num_draft_tokens=cfg.get("engine", "num_draft_tokens"),
                disable_threshold=cfg.get("engine",
                                          "spec_disable_threshold"),
                reenable_after_s=cfg.get("engine",
                                         "spec_reenable_after_s"),
            )
        return LLMEngine(params, model_cfg, tokenizer, engine_cfg,
                         dtype=dtype, mesh=mesh, draft_params=draft_params,
                         draft_cfg=draft_cfg_m, spec=spec, device=device)

    try:
        server = InferenceServer(
            engine_factory,
            tokenizer,
            model_name=cfg.get("model", "model_name"),
            num_engines=cfg.get("server", "num_engines"),
            strategy=cfg.strategy(),
            queue_config=cfg.queue_config(),
            batcher_config=cfg.batcher_config(),
            validator_config=validator_cfg,
            auto_restart=cfg.get("server", "auto_restart"),
            health_check_interval_s=cfg.get("server", "health_check_interval_s"),
            restart_backoff_s=cfg.get("server", "restart_backoff_s"),
            restart_backoff_max_s=cfg.get("server", "restart_backoff_max_s"),
            max_redispatch=cfg.get("server", "max_redispatch"),
            otlp_endpoint=cfg.get("tracing", "otlp_endpoint"),
            otlp_service_name=cfg.get("tracing", "service_name"),
            # disaggregated prefill/decode serving (docs/DISAGG.md)
            engine_roles=cfg.engine_roles(),
            disagg_settings=cfg.disagg_settings(),
            # fleet prefix sharing (docs/CACHING.md): cache_aware
            # route/fetch/recompute cost-model weights
            fetch_costs=cfg.fetch_costs(),
            # multi-host fleet control plane (docs/FLEET.md):
            # fleet.enabled makes this the registry host; fleet.rerole
            # arms the role balancer
            fleet_settings=cfg.fleet_settings(),
            # SLO / performance telemetry (docs/OBSERVABILITY.md
            # "Performance telemetry"): verdicts + /server/perf windows
            slo_settings=cfg.slo_settings(),
            # gray-failure defense (docs/RESILIENCE.md "Gray failures
            # and overload"): latency-scored health + circuit breakers
            # + deadline-aware admission + the shared retry budget
            health_settings=cfg.health_settings(),
            admission_settings=cfg.admission_settings(),
        )
        server.start()
    except (ModelLoadError, RuntimeError, TimeoutError) as e:
        print(f"startup error: {e}", file=sys.stderr)
        return 1
    # what "auto" resolved to, and Mosaic's own words where it refused a
    # kernel: a rejection is an XLA-path server, which must not be quiet
    for runner in server.scheduler.engines():
        pl = runner.placement()
        att = pl["attention"]
        print(f"{runner.engine_id}: devices {pl['device_ids']}, attention "
              f"decode={att['decode']} prefill={att['prefill']}"
              + "".join(f"; {k} kernel rejected: {v}"
                        for k, v in pl["attention_rejected"].items()))

    fleet_worker = None
    if cfg.get("fleet", "connect") or cfg.get("fleet", "registries"):
        # worker mode (docs/FLEET.md): join the registry host(s) — local
        # engines keep serving their own HTTP surface too. With
        # fleet.registries set the worker heartbeats every registry
        # (registry HA dual-heartbeat), so a standby promotes with a
        # warm member table.
        from distributed_inference_server_tpu.serving.remote_runner import (
            FleetWorker,
        )

        fleet_worker = FleetWorker(
            server.scheduler, cfg.fleet_settings(), metrics=server.metrics,
            # fleet-stitched tracing (docs/OBSERVABILITY.md): forwarded
            # requests parent on the wire context and the finished spans
            # ship back to the registry host
            tracer=server.tracer,
        )
        try:
            fleet_worker.start()
        except OSError as e:
            print(f"fleet join failed: {e}", file=sys.stderr)
            server.shutdown()
            return 1
        print(f"joined fleet at {', '.join(fleet_worker.endpoints)} as "
              f"{fleet_worker.member_id}")

    watcher = ConfigWatcher(cfg)
    watcher.subscribe(server.apply_hot_config)
    watcher.start()

    host, port = cfg.get("server", "host"), cfg.get("server", "port")
    grpc_port = cfg.get("server", "grpc_port")
    print(f"serving {cfg.get('model', 'model_name')} on {host}:{port}"
          + (f" (grpc :{grpc_port})" if grpc_port else ""))
    try:
        asyncio.run(server.serve_forever(host, port, grpc_port=grpc_port))
    except KeyboardInterrupt:
        pass
    finally:
        watcher.stop()
        if fleet_worker is not None:
            fleet_worker.stop()
        server.shutdown(drain_timeout_s=cfg.get("server", "drain_timeout_s"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
