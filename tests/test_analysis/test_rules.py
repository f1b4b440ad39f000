"""Per-rule fixture pairs: one violating snippet and its clean twin, each
asserting the exact rule id AND line.  These are the contract of every
graftlint rule — a precision tweak that stops flagging a violating snippet,
or starts flagging a clean one, must show up here first.
"""

import pytest

from tests.test_analysis.conftest import lint_snippet, line_of, rules_of


# ---------------------------------------------------------------------------
# rule 1: use-after-donate
# ---------------------------------------------------------------------------

class TestUseAfterDonate:
    def test_violating_straight_line(self):
        code = """
        def run(compile_once, f, x):
            g = compile_once(f, donate_argnums=(0,))
            y = g(x)
            return x + y  # READ
        """
        findings = lint_snippet(code)
        assert rules_of(findings) == ["use-after-donate"]
        assert findings[0].line == line_of(code, "# READ")
        assert "'x'" in findings[0].message

    def test_clean_rebinding(self):
        code = """
        def run(compile_once, f, x):
            g = compile_once(f, donate_argnums=(0,))
            x = g(x)
            return x
        """
        assert lint_snippet(code) == []

    def test_clean_copy_at_call_site(self):
        code = """
        def run(compile_once, f, x):
            g = compile_once(f, donate_argnums=(0,))
            y = g(x.copy())
            return x + y
        """
        assert lint_snippet(code) == []

    def test_loop_donation_reaches_next_iteration(self):
        code = """
        def run(compile_once, f, x, xs):
            g = compile_once(f, donate_argnums=(0,))
            for _ in range(3):
                y = g(x)  # DONATE, never rebinds x
            return y
        """
        findings = lint_snippet(code)
        assert rules_of(findings) == ["use-after-donate"]
        # the read is x's use in the SECOND loop pass, at the call line
        assert findings[0].line == line_of(code, "# DONATE")

    def test_loop_rebinding_is_clean(self):
        code = """
        def run(fabric, f, params, opt, batch):
            step = fabric.compile(f, donate_argnums=(0, 1))
            for _ in range(10):
                params, opt, aux = step(params, opt, batch)
            return params, opt
        """
        assert lint_snippet(code) == []

    def test_branch_donation_flags_later_read(self):
        code = """
        def run(compile_once, f, x, flag):
            g = compile_once(f, donate_argnums=(0,))
            if flag:
                y = g(x)
            else:
                y = None
            return x  # READ on the path where x was donated
        """
        findings = lint_snippet(code)
        assert rules_of(findings) == ["use-after-donate"]
        assert findings[0].line == line_of(code, "# READ")

    def test_early_return_branch_does_not_leak(self):
        code = """
        def run(compile_once, f, x, flag):
            g = compile_once(f, donate_argnums=(0,))
            if flag:
                return g(x)
            return x
        """
        assert lint_snippet(code) == []

    def test_factory_returned_callable_is_tracked(self):
        """The make_sac_train_fns shape: the donating callable is built in a
        factory and tuple-unpacked by the loop."""
        code = """
        def make_fns(compile_once, act, phase):
            act_fn = compile_once(act)
            train_phase = compile_once(phase, donate_argnums=(0, 1))
            return act_fn, train_phase

        def loop(compile_once, act, phase, params, opt, batch):
            act_fn, train_phase = make_fns(compile_once, act, phase)
            train_phase(params, opt, batch)
            return params  # READ
        """
        findings = lint_snippet(code)
        assert rules_of(findings) == ["use-after-donate"]
        assert findings[0].line == line_of(code, "# READ")

    def test_single_return_factory_is_tracked(self):
        code = """
        def make_step(compile_once, f):
            g = compile_once(f, donate_argnums=(0,))
            return g

        def loop(compile_once, f, x):
            step = make_step(compile_once, f)
            y = step(x)
            return x  # READ
        """
        findings = lint_snippet(code)
        assert rules_of(findings) == ["use-after-donate"]
        assert findings[0].line == line_of(code, "# READ")

    def test_known_fused_builder_is_tracked(self):
        code = """
        def loop(fabric, phase, rb, key, counter):
            dev = fused_uniform_train(fabric, phase, rb, 64, None)
            params, opt = init()
            dev(params, opt, rb.buffers, key, counter)
            return params  # READ
        """
        findings = lint_snippet(code)
        assert rules_of(findings) == ["use-after-donate"]
        assert findings[0].line == line_of(code, "# READ")

    def test_donated_attribute_args_are_skipped(self):
        # rb.buffers at a donated position is not a trackable name — the
        # rule must stay silent rather than guess
        code = """
        def loop(compile_once, f, rb):
            g = compile_once(f, donate_argnums=(0,))
            g(rb.buffers)
            return rb.buffers
        """
        assert lint_snippet(code) == []


class TestPipelineStageDonation:
    """The ISSUE 16 hazard class: compile_stage_pair's backward donates the
    inter-stage activation buffer (arg 1) and the incoming cotangent (arg 2)
    — donate a stage-N output, read it again for the 1F1B backward, and the
    buffer is gone.  Curated-table entry 'compile_stage_pair@1' makes the
    cross-module call sites visible to the flow scan."""

    def test_violating_activation_read_after_backward(self):
        code = """
        def bench_stage(fabric, stage_fn, params, x):
            fwd, bwd = compile_stage_pair(fabric, stage_fn, name="s0")
            act = fwd(params, x)
            dy = fwd(params, x)
            dx = bwd(params, act, dy)
            return act.sum() + dx.sum()  # READ
        """
        findings = lint_snippet(code)
        assert rules_of(findings) == ["use-after-donate"]
        assert findings[0].line == line_of(code, "# READ")
        assert "'act'" in findings[0].message

    def test_violating_cotangent_reused_across_iterations(self):
        # dy built once, donated every pass: dead buffer from pass 2 on
        code = """
        def bench_stage(fabric, stage_fn, params, x, steps):
            fwd, bwd = compile_stage_pair(fabric, stage_fn, name="s0")
            dy = fwd(params, x)
            for _ in range(steps):
                act = fwd(params, x)
                dx = bwd(params, act, dy)  # DONATE
            return dx
        """
        findings = lint_snippet(code)
        assert rules_of(findings) == ["use-after-donate"]
        assert findings[0].line == line_of(code, "# DONATE")
        assert "'dy'" in findings[0].message

    def test_clean_canonical_rebinding_loop(self):
        # the sanctioned shape: act and dy rebound from fwd every pass,
        # params (arg 0) is NOT donated by the backward
        code = """
        def bench_stage(fabric, stage_fn, params, x, steps):
            fwd, bwd = compile_stage_pair(fabric, stage_fn, name="s0")
            for _ in range(steps):
                act = fwd(params, x)
                dy = fwd(params, x)
                dx = bwd(params, act, dy)
            return params, dx
        """
        assert lint_snippet(code) == []

    def test_clean_forward_only(self):
        # fwd (tuple position 0) donates nothing: reuse is legal
        code = """
        def bench_stage(fabric, stage_fn, params, x, steps):
            fwd, bwd = compile_stage_pair(fabric, stage_fn, name="s0")
            act = fwd(params, x)
            act2 = fwd(params, x)
            return act, act2
        """
        assert lint_snippet(code) == []


# ---------------------------------------------------------------------------
# rule 1b: donation-borrowed-buffer
# ---------------------------------------------------------------------------

class TestDonationBorrowedBuffer:
    def test_violating_device_put_numpy(self):
        code = """
        import jax
        import numpy as np

        def run(compile_once, phase, p, o):
            h0 = jax.device_put(np.zeros((4,), np.float32))
            g = compile_once(phase, donate_argnums=(0, 1, 2))
            p, o, h = g(p, o, h0)  # DONATE
            return p, o, h
        """
        findings = lint_snippet(code)
        assert "donation-borrowed-buffer" in rules_of(findings)
        f = next(f for f in findings if f.rule == "donation-borrowed-buffer")
        assert f.line == line_of(code, "# DONATE")
        assert "'h0'" in f.message

    def test_clean_jnp_built_state(self):
        code = """
        import jax.numpy as jnp

        def run(compile_once, phase, p, o):
            h0 = jnp.zeros((4,), jnp.float32)
            g = compile_once(phase, donate_argnums=(0, 1, 2))
            p, o, h = g(p, o, h0)
            return p, o, h
        """
        assert rules_of(lint_snippet(code)) == []


# ---------------------------------------------------------------------------
# rule 2: trace purity
# ---------------------------------------------------------------------------

class TestTracePurity:
    def test_violating_time_call(self):
        code = """
        import time

        def run(fabric):
            def body(p, x):
                t = time.time()  # IMPURE
                return p, x + t
            return fabric.compile(body, donate_argnums=(0,))
        """
        findings = lint_snippet(code)
        assert rules_of(findings) == ["trace-impure-time"]
        assert findings[0].line == line_of(code, "# IMPURE")

    def test_violating_python_branch(self):
        code = """
        def run(compile_once):
            def body(p, x):
                if x > 0:  # BRANCH
                    return p, x
                return p, -x
            return compile_once(body)
        """
        findings = lint_snippet(code)
        assert rules_of(findings) == ["trace-python-branch"]
        assert findings[0].line == line_of(code, "# BRANCH")

    def test_violating_host_concretize(self):
        code = """
        import numpy as np

        def run(compile_once):
            def body(p, x):
                a = float(x)     # CONCRETIZE
                b = np.abs(x)    # NUMPY
                return p, a + b
            return compile_once(body)
        """
        findings = lint_snippet(code)
        assert rules_of(findings) == ["trace-host-concretize", "trace-host-concretize"]
        assert findings[0].line == line_of(code, "# CONCRETIZE")
        assert findings[1].line == line_of(code, "# NUMPY")

    def test_clean_partial_jit_static_argnums_decorator(self):
        code = """
        from functools import partial
        import jax

        @partial(jax.jit, static_argnums=(2,))
        def body(p, x, greedy):
            if greedy:
                return p, x
            return p, -x
        """
        assert lint_snippet(code) == []

    def test_clean_static_argname_branch(self):
        code = """
        def run(compile_once):
            def body(p, x, greedy=False):
                if greedy:
                    return p, x
                return p, -x
            return compile_once(body, static_argnames=("greedy",))
        """
        assert lint_snippet(code) == []

    def test_clean_structural_tests_and_jnp(self):
        code = """
        import jax.numpy as jnp

        def run(compile_once):
            def body(p, x):
                if isinstance(x, dict):
                    x = x["a"]
                if x is None:
                    return p, None
                if x.ndim == 3:
                    x = x[None]
                return p, jnp.where(x > 0, x, -x)
            return compile_once(body)
        """
        assert lint_snippet(code) == []

    def test_untraced_function_is_not_checked(self):
        code = """
        import time

        def host_only(x):
            if x > 0:
                return time.time()
            return float(x)
        """
        assert lint_snippet(code) == []

    def test_lax_scan_body_is_traced(self):
        code = """
        import time
        from jax import lax

        def run(carry, xs):
            def step(c, x):
                t = time.time()  # IMPURE
                return c, x + t
            return lax.scan(step, carry, xs)
        """
        findings = lint_snippet(code)
        assert rules_of(findings) == ["trace-impure-time"]
        assert findings[0].line == line_of(code, "# IMPURE")


# ---------------------------------------------------------------------------
# rule 3: PRNG discipline
# ---------------------------------------------------------------------------

class TestPrng:
    def test_violating_two_sinks(self):
        code = """
        import jax

        def run(key):
            a = jax.random.normal(key, (4,))
            b = jax.random.uniform(key, (4,))  # REUSE
            return a, b
        """
        findings = lint_snippet(code)
        assert rules_of(findings) == ["prng-key-reuse"]
        assert findings[0].line == line_of(code, "# REUSE")

    def test_clean_split_and_thread(self):
        code = """
        import jax

        def run(key):
            k1, k2 = jax.random.split(key)
            a = jax.random.normal(k1, (4,))
            b = jax.random.uniform(k2, (4,))
            return a, b
        """
        assert lint_snippet(code) == []

    def test_use_after_split_is_reuse(self):
        code = """
        import jax

        def run(key):
            k1, k2 = jax.random.split(key)
            return jax.random.normal(key, (4,))  # REUSE
        """
        findings = lint_snippet(code)
        assert rules_of(findings) == ["prng-key-reuse"]
        assert findings[0].line == line_of(code, "# REUSE")

    def test_loop_consumption_without_rebind(self):
        code = """
        import jax

        def run(key, n):
            out = []
            for _ in range(n):
                out.append(jax.random.normal(key, (4,)))  # REUSE (every iter)
            return out
        """
        findings = lint_snippet(code)
        assert rules_of(findings) == ["prng-key-reuse"]
        assert findings[0].line == line_of(code, "# REUSE")

    def test_loop_with_threading_is_clean(self):
        code = """
        import jax

        def run(key, n):
            out = []
            for _ in range(n):
                key, k = jax.random.split(key)
                out.append(jax.random.normal(k, (4,)))
            return out
        """
        assert lint_snippet(code) == []

    def test_fold_in_does_not_consume(self):
        code = """
        import jax

        def run(key, n):
            keys = [jax.random.fold_in(key, i) for i in range(n)]
            k1, k2 = jax.random.split(key)
            return keys, k1, k2
        """
        assert lint_snippet(code) == []

    def test_branches_do_not_pair(self):
        # the sac-loop shape: if/else arms each consume tk once
        code = """
        import jax

        def run(train_a, train_b, key, flag):
            key, tk = jax.random.split(key)
            if flag:
                out = train_a(tk)
            else:
                out = train_b(tk)
            return out
        """
        assert lint_snippet(code) == []

    def test_early_return_does_not_pair(self):
        code = """
        import jax

        def sample(dist, key, continuous):
            if continuous:
                return dist.sample(key)
            keys = jax.random.split(key, 3)
            return [dist.sample(k) for k in keys]
        """
        assert lint_snippet(code) == []

    def test_consume_after_both_branches_consumed(self):
        code = """
        import jax

        def run(train_a, train_b, key, flag):
            key, tk = jax.random.split(key)
            if flag:
                out = train_a(tk)
            else:
                out = train_b(tk)
            return out, train_a(tk)  # REUSE
        """
        findings = lint_snippet(code)
        assert rules_of(findings) == ["prng-key-reuse"]
        assert findings[0].line == line_of(code, "# REUSE")

    def test_split_discarded(self):
        code = """
        import jax

        def run(key):
            jax.random.split(key)  # DISCARD
            return jax.random.normal(key, (4,))
        """
        findings = lint_snippet(code)
        assert "prng-split-discarded" in rules_of(findings)
        f = next(f for f in findings if f.rule == "prng-split-discarded")
        assert f.line == line_of(code, "# DISCARD")

    def test_key_named_int_param_is_not_a_key(self):
        # copies_per_key is an int; builtins must not count as sinks
        code = """
        def estimate(copies_per_key):
            a = int(copies_per_key)
            b = int(copies_per_key) * 2
            return a + b
        """
        assert lint_snippet(code) == []


# ---------------------------------------------------------------------------
# rule 4: registries (uses the real repo config tree / fault registry)
# ---------------------------------------------------------------------------

class TestRegistries:
    def test_cfg_known_key_is_clean(self, repo_ctx):
        code = """
        def run(cfg):
            return cfg.algo.total_steps, cfg.buffer.size, cfg.env.num_envs
        """
        assert lint_snippet(code, ctx=repo_ctx) == []

    def test_cfg_unknown_key_flags(self, repo_ctx):
        code = """
        def run(cfg):
            return cfg.algo.learning_startss  # TYPO
        """
        findings = lint_snippet(code, ctx=repo_ctx)
        assert rules_of(findings) == ["cfg-unknown-key"]
        assert findings[0].line == line_of(code, "# TYPO")
        assert "algo.learning_startss" in findings[0].message

    def test_cfg_optional_get_is_never_an_error(self, repo_ctx):
        code = """
        def run(cfg):
            return cfg.algo.get("definitely_not_a_key"), cfg.get("nope", 1)
        """
        assert lint_snippet(code, ctx=repo_ctx) == []

    def test_cfg_leaf_value_methods_are_not_keys(self, repo_ctx):
        code = """
        def run(cfg):
            return cfg.buffer.device.lower()
        """
        assert lint_snippet(code, ctx=repo_ctx) == []

    def test_fault_site_known_is_clean(self, repo_ctx):
        code = """
        from sheeprl_tpu.resilience.faults import fault_point

        def run():
            fault_point("env.step")
        """
        assert lint_snippet(code, ctx=repo_ctx) == []

    def test_fault_site_typo_flags(self, repo_ctx):
        code = """
        from sheeprl_tpu.resilience.faults import fault_point

        def run():
            fault_point("env.stpe")  # TYPO
        """
        findings = lint_snippet(code, ctx=repo_ctx)
        assert rules_of(findings) == ["fault-site-unknown"]
        assert findings[0].line == line_of(code, "# TYPO")

    def test_fault_spec_dict_and_kwarg_checked(self, repo_ctx):
        code = """
        def plan(FaultSpec):
            a = FaultSpec(site="serve.htpp", kind="raise", at=1)  # KWARG
            b = {"site": "env.reset", "at": 2}
            c = {"site": "checkpoint.commmit", "every": 3}  # DICT
            return a, b, c
        """
        findings = lint_snippet(code, ctx=repo_ctx)
        assert rules_of(findings) == ["fault-site-unknown", "fault-site-unknown"]
        assert findings[0].line == line_of(code, "# KWARG")
        assert findings[1].line == line_of(code, "# DICT")

    def test_retry_site_label_is_not_a_fault_site(self, repo_ctx):
        # retry()'s site= labels Resilience/* metrics — a different registry
        code = """
        def run(retry, job):
            return retry(job, attempts=3, site="checkpoint.write")
        """
        assert lint_snippet(code, ctx=repo_ctx) == []

    def test_metric_documented_family_is_clean(self, repo_ctx):
        code = """
        def run(aggregator, logger):
            aggregator.update("Loss/value_loss", 1.0)
            logger.log_metrics({"Rewards/rew_avg": 1.0}, 0)
        """
        assert lint_snippet(code, ctx=repo_ctx) == []

    def test_metric_unknown_family_flags(self, repo_ctx):
        code = """
        def run(aggregator, metrics):
            aggregator.update("Bogus/value", 1.0)  # AGG
            metrics["AlsoBogus/x"] = 2.0  # STORE
        """
        findings = lint_snippet(code, ctx=repo_ctx)
        assert rules_of(findings) == ["metric-family-unknown", "metric-family-unknown"]
        assert findings[0].line == line_of(code, "# AGG")
        assert findings[1].line == line_of(code, "# STORE")

    def test_non_metric_slash_strings_ignored(self, repo_ctx):
        code = """
        def run(d):
            protocol_version = "HTTP/1.1"
            d["some/path/like/thing"] = 1
            return protocol_version
        """
        assert lint_snippet(code, ctx=repo_ctx) == []
