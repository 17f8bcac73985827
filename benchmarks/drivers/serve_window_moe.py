"""Driver of the generative serving path for a ``cohere2_moe``
configuration: ``serve_gen.Run``'s arrival loop and window as they are;
the deployment (``WindowMoEGenModel`` from the published keys, chunked
prefill over the contiguous cache tree), the counters (the expert
layers' routed pairs and the cache rows the decode steps saw, read from
the engine at the window's edges), the sample (long and short prompts
both), the reference's arguments and a second reading of the comparison
(the mean gap beside the widest) differ.
"""

import numpy

from benchmarks.drivers import serve_gen
from benchmarks.drivers.serve_hybrid import _between

KINDS = {"sliding_attention": "W", "full_attention": "F"}


def program_config(config):
    """The configuration in ``samples/window_moe_lm.py``'s keys."""
    depth = config["num_hidden_layers"]
    out = {
        "vocab": config["vocab_size"], "dim": config["hidden_size"],
        "pattern": "".join(KINDS[kind]
                           for kind in config["layer_types"][:depth]),
        "seq_len": config["engine"]["max_seq"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "window": config["sliding_window"],
        "rope_theta": float(config["rope_theta"]),
        "router_width": config["router_width"],
        "experts_held": config["num_experts"],
        "held_from": config.get("held_from", 0),
        "top_k": config["num_experts_per_tok"],
        "expert_width": config["intermediate_size"],
        "shared_experts": config["num_shared_experts"],
        "norm_eps": float(config["layer_norm_eps"]),
        "logit_scale": float(config["logit_scale"]),
    }
    if "init_gain" in config:
        out["init_gain"] = config["init_gain"]
    return out


class Run(serve_gen.Run):
    def _deploy(self):
        import jax
        import jax.numpy as jnp
        from veles_tpu.gen import GenerativeEngine, WindowMoEGenModel
        from veles_tpu.samples import window_moe_lm
        from veles_tpu.serve import ModelRegistry

        config = self.ctx.config
        pcfg = program_config(config)
        dtype = jnp.dtype(config["dtype"])
        self.params = self.reference.init_params(config, self.ctx.seed,
                                                 dtype)
        want = jax.tree.map(lambda s: (s.shape, jnp.dtype(s.dtype)),
                            window_moe_lm.param_shapes(pcfg, dtype))
        have = jax.tree.map(lambda a: (a.shape, a.dtype), self.params)
        if want != have:
            raise RuntimeError("the reference's parameter layout is not "
                               "the program's: %r vs %r" % (have, want))
        model = WindowMoEGenModel(pcfg, compute_dtype=dtype.type)
        eng = config["engine"]
        chunk = eng["prefill_chunk"]
        # the arrival loop warms one request a bucket: with chunks the
        # one program that takes a prompt is the chunk's
        self.engine = GenerativeEngine(
            model, params=self.params, max_slots=eng["max_slots"],
            max_seq=eng["max_seq"], prefill_buckets=(chunk,),
            prefill_chunk=chunk, kv=eng["kv"], seed=0)
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
        kinds = config["layer_types"][:config["num_hidden_layers"]]
        window = kinds.count("sliding_attention")
        for stretch in (obs["counters"], obs.get("traced", {})):
            counted = stretch.get("hybrid")
            if not counted or not counted["decode_calls"]:
                continue
            # what a held expert sees a decode step
            counted["tokens_per_held_expert"] = \
                counted["decode"]["moe_local_pairs"] / float(
                    config["num_experts"] * len(kinds)
                    * counted["decode_calls"])
            # the rows the window layers saw, in percent of those they
            # would have seen had they kept every position
            host = counted["host"]
            if host["kv_rows_full"] and window < len(kinds):
                counted["window_rows_share"] = \
                    100.0 * host["kv_rows_window"] / float(
                        host["kv_rows_full"] * window
                        / (len(kinds) - window))
        return obs

    def sample(self):
        """Requests the window finished: ``verify_long`` of those whose
        prompt is longer than its ``above`` (the ring has wrapped and
        chunks have crossed it), ``verify_short`` of those shorter than
        its ``below``, the rest from all, each drawn from the seed."""
        params = self.ctx.params
        done = [r for r in self.due_in
                if r.get("served") is not None and r["served"]]
        rng = numpy.random.default_rng([self.ctx.seed, 3])
        done = [done[i] for i in rng.permutation(len(done))]
        long_, short = params["verify_long"], params["verify_short"]
        picks = [r for r in done
                 if len(r["tokens"]) > long_["above"]][:long_["count"]]
        picks += [r for r in done
                  if len(r["tokens"]) < short["below"]][:short["count"]]
        rest = [r for r in done if not any(r is p for p in picks)]
        return picks + rest[:max(int(params["verify_sample"])
                                 - len(picks), 0)]

    def _readings(self, quant=None, sample=None):
        """Over the sample: the WIDEST gap by which a served token's
        reference logit lies below the reference's best, and the MEAN
        gap over all compared tokens.  The widest is a tail: one token
        whose router put a near-tie the other way reads like a lower
        precision does; the mean is what a lower precision moves."""
        gaps = [self._gaps_of(record, record["served"], quant)
                for record in (self.sample() if sample is None
                               else sample)]
        if not gaps:
            return {"logit_gap": float("nan"),
                    "logit_gap_mean": float("nan")}, 0
        gaps = numpy.concatenate(gaps)
        return {"logit_gap": float(gaps.max()),
                "logit_gap_mean": float(gaps.mean())}, len(gaps)

    def controls(self):
        out = {"fp8": self._readings("fp8")[0]}
        sample = self.sample()
        if sample:
            served = list(sample[0]["served"])
            middle = len(served) // 2
            served[middle] = (served[middle] + 1) \
                % self.ctx.config["vocab_size"]
            out["altered_token"] = self._readings(
                sample=[dict(sample[0], served=served)])[0]
        return out

    def verify(self):
        readings, tokens = self._readings()
        self.ctx.log("compared %d served tokens" % tokens)
        return dict(readings, unanswered=self.obs["failed"],
                    compiles_in_window=self.obs["compiles_in_window"])

    def _gaps_of(self, record, served, quant=None):
        spec = self.ctx.params["traffic_spec"]
        return self.reference.served_gaps(
            self.params, self.ctx.config, record["tokens"], served,
            int(self.ctx.params["verify_pad_from"]),
            int(spec["output_len"]["max"]), quant)
