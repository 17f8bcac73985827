"""Driver of the generative serving path for a ``nemotron_h``
configuration: ``serve_gen.Run``'s arrival loop, window and sample as
they are; only the deployment (``HybridGenModel`` from the published
keys), the counters (the expert layers' routed pairs, read from the
engine at the window's edges) and the reference's arguments differ.
"""

from benchmarks.drivers import serve_gen


def program_config(config):
    """The configuration in ``samples/hybrid_lm.py``'s keys."""
    depth = config["num_hidden_layers"]
    return {
        "vocab": config["vocab_size"], "dim": config["hidden_size"],
        "pattern": config["hybrid_override_pattern"][:depth],
        "seq_len": config["engine"]["max_seq"],
        "ssm_heads": config["mamba_num_heads"],
        "ssm_head_dim": config["mamba_head_dim"],
        "ssm_groups": config["n_groups"],
        "ssm_state": config["ssm_state_size"],
        "conv_kernel": config["conv_kernel"],
        "chunk": config["chunk_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "router_width": config["router_width"],
        "experts_held": config["n_routed_experts"],
        "held_from": config.get("held_from", 0),
        "top_k": config["num_experts_per_tok"],
        "latent": config["moe_latent_size"],
        "expert_width": config["moe_intermediate_size"],
        "shared_width": config["moe_shared_expert_intermediate_size"],
        "routed_scale": float(config["routed_scaling_factor"]),
        "norm_eps": float(config["norm_eps"]),
        "published_layers": config.get("published", {}).get(
            "num_hidden_layers", depth),
    }


class Run(serve_gen.Run):
    def _deploy(self):
        import jax
        import jax.numpy as jnp
        from veles_tpu.gen import GenerativeEngine, HybridGenModel
        from veles_tpu.samples import hybrid_lm
        from veles_tpu.serve import ModelRegistry

        config = self.ctx.config
        pcfg = program_config(config)
        dtype = jnp.dtype(config["dtype"])
        self.params = self.reference.init_params(config, self.ctx.seed,
                                                 dtype)
        want = jax.tree.map(lambda s: (s.shape, jnp.dtype(s.dtype)),
                            hybrid_lm.param_shapes(pcfg, dtype))
        have = jax.tree.map(lambda a: (a.shape, a.dtype), self.params)
        if want != have:
            raise RuntimeError("the reference's parameter layout is not "
                               "the program's: %r vs %r" % (have, want))
        model = HybridGenModel(pcfg, compute_dtype=dtype.type)
        eng = config["engine"]
        self.engine = GenerativeEngine(
            model, params=self.params, max_slots=eng["max_slots"],
            max_seq=eng["max_seq"],
            prefill_buckets=tuple(eng["prefill_buckets"]),
            kv=eng.get("kv", "contiguous"), seed=0)
        self.registry = ModelRegistry()
        deployed = self.registry.deploy_generative(
            "lm", self.engine,
            scheduler_config=dict(config.get("scheduler", {})))
        self.scheduler = deployed.scheduler

    def _counters(self):
        out = serve_gen.Run._counters(self)
        out["hybrid"] = {kind: dict(values) for kind, values
                         in self.engine.counters.items()}
        self._marks.append(out)
        return out

    def run(self):
        self._marks = []
        obs = serve_gen.Run.run(self)
        # the marks in the order the arrival loop passed them: open,
        # (the trace's stop,) close
        opened, closed = self._marks[0], self._marks[-1]
        obs["counters"]["hybrid"] = _between(opened, closed)
        if "traced" in obs:
            obs["traced"]["hybrid"] = _between(opened, self._marks[1])
        config = self.ctx.config
        layers = config["hybrid_override_pattern"][
            :config["num_hidden_layers"]].count("E")
        for stretch in (obs["counters"], obs.get("traced", {})):
            counted = stretch.get("hybrid")
            if counted and counted["decode_calls"]:
                # what a held expert sees a decode step
                counted["tokens_per_held_expert"] = \
                    counted["decode"]["moe_local_pairs"] / float(
                        config["n_routed_experts"] * layers
                        * counted["decode_calls"])
        return obs

    def _gaps_of(self, record, served, quant=None):
        spec = self.ctx.params["traffic_spec"]
        max_rows = int(spec["output_len"]["max"])
        return self.reference.served_gaps(
            self.params, self.ctx.config, record["tokens"], served,
            int(spec["prompt_len"]["max"]) + max_rows, max_rows, quant)


def _between(lo, hi):
    """The engine's counters over a stretch: sums by difference, a
    ``_max`` as it stood at the stretch's end; beside them the decode
    steps and prefills they were counted over."""
    out = {"decode_calls": hi["decode_calls"] - lo["decode_calls"],
           "prefill_calls": hi["prefill_calls"] - lo["prefill_calls"],
           "seconds": hi["t"] - lo["t"]}
    for kind, values in hi["hybrid"].items():
        out[kind] = {
            name: value if name.endswith("_max")
            else value - lo["hybrid"][kind][name]
            for name, value in values.items()}
    return out

