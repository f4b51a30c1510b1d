#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system starts on the chip.

    python3 chip_smoke.py          # on a machine with one TPU chip (or more)

Drives the two main paths once, through the entry points a user calls,
at the full width and depth of the one LM the repo supports (1024d x
8L, 111.2M parameters — PERF.md section 4; random weights from a
seed):

- serve leg: `python -m deeplearning4j_tpu.cli serve` as a child
  process, then `/predict` and `/generate` over HTTP (prompts on both
  sides of the 128-token flash threshold, a concurrent burst, one
  repeated prompt that must hit the prefix cache), `/stats`, stop.
- train leg: a second child, after the first has exited — the naive
  full-recompute reference decode the serve leg's tokens are compared
  with, then `models.transformer.make_train_step` in bf16 at B8 x T1024
  with the flash forward AND backward kernels asserted in the program,
  then (on >= 4 devices) the data-parallel trainers on a real mesh.

This parent process is stdlib only and never imports JAX: a chip
belongs to one process at a time, so the legs run as children, one
after the other, with the platform pinned — a missing or refused TPU
is an error, never a smaller run on the CPU. There is no CPU mode and
no switch; the leg functions take their sizes as arguments so that
tests/test_chip_smoke.py can drive them tiny.

Exit code 0 and, as the last line of standard output,
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`
only when every phase passed. Anything else exits non-zero and prints
no result line.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

#: the largest LM the repo has run (PERF.md section 4): 111.2M params
LM = {"vocab_size": 8192, "d_model": 1024, "n_heads": 8, "n_layers": 8,
      "d_ff": 4096, "max_len": 2048, "seed": 0}
#: the bench MLP (784-2048-1024-10): `/predict`, and the four-chip leg
MLP = (784, 2048, 1024, 10)
#: prompt lengths on both sides of the 128-token flash threshold
PROMPT_LENS = (20, 200, 1100)
NEW_TOKENS = 32


class SmokeFailure(Exception):
    """A phase of the smoke did not do what it must."""


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def say(message: str) -> None:
    print(f"[chip_smoke] {message}", flush=True)


# ------------------------------------------------------------- children
def child_env(platform: str) -> dict:
    """The parent's environment with the platform pinned: JAX carries
    on from the CPU when a TPU back end fails to start and
    `JAX_PLATFORMS` is unset, and that must be an error here."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = platform
    return env


def _sigint_default() -> None:
    # a parent started by `cmd &` from a non-interactive shell has
    # SIGINT ignored, and would hand that to the child we stop with it
    signal.signal(signal.SIGINT, signal.SIG_DFL)


class Child:
    """One child process: stdout collected line by line on a thread
    (so it never blocks on a full pipe), stderr kept in a file whose
    tail is shown when the child fails."""

    def __init__(self, argv, platform: str, name: str):
        self.name = name
        self.platform = platform
        self._err = tempfile.TemporaryFile(mode="w+")
        self.proc = subprocess.Popen(
            argv, cwd=HERE, env=child_env(platform), text=True,
            stdout=subprocess.PIPE, stderr=self._err,
            preexec_fn=_sigint_default)
        self.lines: list = []
        self._cond = threading.Condition()
        self._eof = False
        self._thread = threading.Thread(target=self._drain, daemon=True)
        self._thread.start()

    def _drain(self) -> None:
        for line in self.proc.stdout:
            with self._cond:
                self.lines.append(line.rstrip("\n"))
                self._cond.notify_all()
        with self._cond:
            self._eof = True
            self._cond.notify_all()

    def json_line(self, key: str, timeout: float) -> dict:
        """The first stdout line that is a JSON object holding `key`."""
        deadline = time.monotonic() + timeout
        seen = 0
        with self._cond:
            while True:
                for line in self.lines[seen:]:
                    if line.lstrip().startswith("{"):
                        try:
                            obj = json.loads(line)
                        except ValueError:
                            continue
                        if isinstance(obj, dict) and key in obj:
                            return obj
                seen = len(self.lines)
                left = deadline - time.monotonic()
                if self._eof or left <= 0:
                    break
                self._cond.wait(left)
        self.fail(f"exited before printing its {key!r} line" if self._eof
                  else f"no {key!r} line within {timeout:.0f}s")

    def stderr_tail(self, n: int = 12) -> str:
        self._err.flush()
        self._err.seek(0)
        return "".join(self._err.readlines()[-n:])

    def fail(self, what: str) -> None:
        if self._eof:   # stdout closed: the exit code is a moment away
            try:
                self.proc.wait(10)
            except subprocess.TimeoutExpired:
                pass
        rc = self.proc.poll()
        tail = self.stderr_tail()
        hint = ""
        if "Unable to initialize backend" in tail:
            hint = (f"\nNO {self.platform.upper()} HERE: JAX could not start "
                    f"the {self.platform!r} back end on this machine, and "
                    "chip_smoke.py has no CPU mode.")
        raise SmokeFailure(
            f"{self.name} child: {what} (exit code {rc}){hint}\n"
            f"--- last lines of its stderr ---\n{tail}")

    def wait(self, timeout: float) -> int:
        try:
            rc = self.proc.wait(timeout)
        except subprocess.TimeoutExpired:
            self.fail(f"still running after {timeout:.0f}s")
        self._thread.join(10)
        return rc

    def kill(self) -> None:
        """Make sure nothing this smoke started outlives it."""
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._err.close()


def check_device(device: dict, platform: str, who: str) -> dict:
    check(isinstance(device, dict)
          and {"platform", "kind", "count"} <= set(device),
          f"{who} did not name its device: {device!r}")
    check(device["platform"] == platform,
          f"{who} runs on {device['platform']!r}, not {platform!r} — "
          "a smoke on the wrong device is a failure, not a smaller run")
    return {k: device[k] for k in ("platform", "kind", "count")}


def cache_entries(path) -> int:
    try:
        return len(os.listdir(path)) if path else 0
    except OSError:
        return 0


def cache_state(before: int, after: int) -> str:
    """warm = a non-empty compile cache that this leg added nothing to."""
    if not before:
        return f"cold (0 entries -> {after})"
    if after > before:
        return f"partly warm ({before} entries -> {after})"
    return f"warm ({before} entries, none added)"


# ------------------------------------------------------------ serve leg
def _post(url: str, payload: dict, timeout: float) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            check(resp.status == 200, f"POST {url} -> {resp.status}")
            return json.loads(resp.read())
    except urllib.error.HTTPError as e:
        raise SmokeFailure(
            f"POST {url} -> {e.code}: {e.read().decode()[:500]}") from None


def _get(url: str, timeout: float = 60.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        check(resp.status == 200, f"GET {url} -> {resp.status}")
        return json.loads(resp.read())


def make_prompt(length: int, vocab: int, salt: int) -> list:
    """A fixed pseudo-random token row (no RNG: the train child rebuilds
    the same row for the reference decode)."""
    return [(salt * 7919 + i * 104729 + (i * i) % 8191) % vocab
            for i in range(length)]


def mlp_conf(sizes) -> dict:
    """A MultiLayerConfiguration for `cli serve -m` — dense relu layers
    and a softmax output; every other field takes its default."""
    confs = [{"n_in": a, "n_out": b, "activation_function": "relu"}
             for a, b in zip(sizes[:-2], sizes[1:-1])]
    confs.append({"layer": "output", "n_in": sizes[-2], "n_out": sizes[-1],
                  "activation_function": "softmax",
                  "loss_function": "mcxent"})
    return {"confs": confs, "hidden_layer_sizes": list(sizes[1:-1]),
            "pretrain": False}


def _generate(url: str, prompt: list, new_tokens: int,
              timeout: float) -> list:
    out = _post(url + "/generate",
                {"prompt": [prompt], "max_tokens": new_tokens}, timeout)
    row = out["tokens"][0]
    check(row[:len(prompt)] == prompt,
          "/generate did not echo the prompt it was given")
    tail = row[len(prompt):]
    check(len(tail) == new_tokens,
          f"/generate returned {len(tail)} new tokens, asked {new_tokens} "
          f"(finish_reasons {out.get('finish_reasons')})")
    return tail


def serve_leg(lm: dict = LM, mlp=MLP, prompt_lens=PROMPT_LENS,
              new_tokens: int = NEW_TOKENS, platform: str = "tpu",
              serve_args=(), timeout: float = 600.0) -> dict:
    """Start `cli serve`, answer a `/predict` and several `/generate`s,
    read `/stats`, stop the server. Returns the leg's report (the first
    prompt's tokens included, for the reference check)."""
    short, mid, long_ = (make_prompt(n, lm["vocab_size"], salt)
                         for salt, n in enumerate(prompt_lens, 1))
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        conf_path = os.path.join(tmp, "mlp_conf.json")
        with open(conf_path, "w") as f:
            json.dump(mlp_conf(mlp), f)
        child = Child(
            [sys.executable, "-m", "deeplearning4j_tpu.cli", "serve",
             "-m", conf_path, "--transformer", json.dumps(lm),
             "--port", "0", *serve_args],
            platform, "serve")
        try:
            t0 = time.monotonic()
            announce = child.json_line("serving", timeout)
            t_start = time.monotonic() - t0
            device = check_device(announce.get("device"), platform,
                                  "cli serve")
            selected = announce["decode"]["kernel"]["selected"]
            check(selected == "pallas",
                  f"decode.kernel.selected is {selected!r}, not 'pallas': "
                  "the decode step does not run the paged kernel")
            url = announce["serving"]
            jax_cache = announce.get("jax_cache") or {}

            x = [[((r * 31 + c) % 17) / 17.0 for c in range(mlp[0])]
                 for r in range(4)]
            t0 = time.monotonic()
            out = _post(url + "/predict", {"inputs": x}, timeout)
            t_predict = time.monotonic() - t0
            probs = out["outputs"]
            check(len(probs) == 4 and all(len(p) == mlp[-1] for p in probs),
                  "/predict output has the wrong shape")
            check(all(abs(sum(p) - 1.0) < 1e-2 and min(p) >= 0.0
                      for p in probs),
                  "/predict rows are not probability vectors")

            # first request: compiles a prefill bucket and THE decode step
            t0 = time.monotonic()
            first = _generate(url, short, new_tokens, timeout)
            t_first = time.monotonic() - t0
            # a concurrent burst with different budgets: slots join and
            # leave the one decode program at different steps
            burst = [(mid, new_tokens), (long_, new_tokens + 16),
                     (short[::-1], new_tokens + 8)]
            results: list = [None] * len(burst)

            def run(i, prompt, n):
                try:
                    results[i] = _generate(url, prompt, n, timeout)
                except BaseException as e:   # re-raised below
                    results[i] = e

            t0 = time.monotonic()
            threads = [threading.Thread(target=run, args=(i, p, n))
                       for i, (p, n) in enumerate(burst)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout)
            t_burst = time.monotonic() - t0
            for r in results:
                if isinstance(r, BaseException):
                    raise r
                check(r is not None, "a burst request never returned")
            # the same long prompt again: its full pages are cached, so
            # only the tail is prefilled (paged_kinds.prefill_ctx), and greedy
            # decoding must give the same tokens
            t0 = time.monotonic()
            again = _generate(url, long_, new_tokens + 16, timeout)
            t_repeat = time.monotonic() - t0
            check(again == results[1],
                  "the repeated prompt (prefix-cache hit) produced "
                  "different tokens than its first run")
            # the short prompt again: its one full page is cached too
            # (another prefill_ctx shape), then once more with every
            # program it needs already compiled — the steady time
            for _ in range(2):
                t0 = time.monotonic()
                steady = _generate(url, short, new_tokens, timeout)
                t_steady = time.monotonic() - t0
                check(steady == first,
                      "the same prompt gave different tokens")

            stats = _get(url + "/stats")
            decode = stats["generate"]["decode"]
            check(decode["decode_step_programs"] == 1,
                  f"{decode['decode_step_programs']} decode-step programs, "
                  "not 1: membership changes recompiled the step")
            check(decode["decode_kernel"]["selected"] == "pallas",
                  "/stats disagrees with the announce line on the kernel")
            check(decode["prefix_cache"]["hits"] >= 1
                  and decode["prefill_ctx_programs"] >= 1,
                  "the repeated prompt did not hit the prefix cache")
            check(decode["requests"] >= 7, "not every request was counted")

            child.proc.send_signal(signal.SIGINT)
            rc = child.wait(60.0)
            check(rc == 0, f"cli serve exited with code {rc} when stopped")
        finally:
            child.kill()
    entries = cache_entries(jax_cache.get("dir"))
    report = {
        "device": device,
        "kernel": selected,
        "first_tokens": first,
        "seconds": {"start": round(t_start, 2),
                    "predict": round(t_predict, 2),
                    "first_generate_with_compile": round(t_first, 2),
                    "burst_with_compiles": round(t_burst, 2),
                    "repeat_prefix_hit": round(t_repeat, 2),
                    "steady_generate": round(t_steady, 3)},
        "steady_tokens_per_s": round(new_tokens / t_steady, 1),
        "compile_cache": cache_state(
            int(jax_cache.get("entries_at_start") or 0), entries),
        "stats": {k: decode[k] for k in
                  ("decode_step_programs", "prefill_programs",
                   "prefill_ctx_programs", "dispatches", "requests",
                   "tokens_streamed")},
        "prefix_hits": decode["prefix_cache"]["hits"],
    }
    say(f"serve leg ok on {device}: " + json.dumps(
        {k: v for k, v in report.items()
         if k not in ("device", "first_tokens")}))
    return report


# ------------------------------------------------------------ train leg
def train_leg(lm: dict = LM, batch: int = 8, tokens_per_row: int = 1025,
              steps: int = 4, ref_prompt_len: int = PROMPT_LENS[0],
              ref_new_tokens: int = NEW_TOKENS, mlp=MLP,
              mlp_batch: int = 4096, platform: str = "tpu",
              timeout: float = 900.0) -> dict:
    """Run `_train_child` in a fresh process and return its report."""
    job = {"lm": lm, "batch": batch, "tokens_per_row": tokens_per_row,
           "steps": steps, "ref_prompt_len": ref_prompt_len,
           "ref_new_tokens": ref_new_tokens, "mlp": list(mlp),
           "mlp_batch": mlp_batch, "platform": platform}
    child = Child(
        [sys.executable, "-c",
         "import json, sys, chip_smoke; "
         "chip_smoke._train_child(json.loads(sys.argv[1]))",
         json.dumps(job)],
        platform, "train")
    try:
        hello = child.json_line("device", timeout)
        device = check_device(hello["device"], platform, "the train child")
        say(f"train child: {json.dumps(hello)}")
        report = child.json_line("train_report", timeout)["train_report"]
        rc = child.wait(120.0)
        check(rc == 0, f"train child exited with code {rc}")
    finally:
        child.kill()
    cache = hello.get("compile_cache") or {}
    report["device"] = device
    report["compile_cache"] = cache_state(
        int(cache.get("entries_at_start") or 0),
        cache_entries(cache.get("dir")))
    say("train leg ok: " + json.dumps(
        {k: v for k, v in report.items() if k != "reference_tokens"}))
    return report


def _train_child(job: dict) -> None:
    """Runs in the train leg's own process — the only code in this file
    that touches JAX. Any failed check raises: the process exits
    non-zero and the parent fails the smoke."""
    from deeplearning4j_tpu.utils import jaxenv

    jaxenv.configure()
    cache_at_start = jaxenv.compile_cache_entries()

    import jax
    import jax.numpy as jnp
    import jaxlib
    import numpy as np

    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig, generate, init_transformer_params,
        init_velocity, make_train_step)
    from deeplearning4j_tpu.runtime import native_available

    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "?")
    except ImportError:
        libtpu_version = None
    device = jaxenv.device_report()
    print(json.dumps({
        "device": device,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu_version},
        "native_library_loaded": native_available(),
        "compile_cache": {"dir": os.environ.get(jaxenv.CACHE_ENV),
                          "entries_at_start": cache_at_start}}),
        flush=True)
    check(device["platform"] == job["platform"],
          f"on {device['platform']!r}, not {job['platform']!r}")
    on_tpu = device["platform"] == "tpu"
    report: dict = {}

    # -- the serve leg's reference: same seed, same weights, the naive
    # full-recompute greedy decode through transformer_logits
    lm = dict(job["lm"])
    seed = int(lm.pop("seed", 0))
    cfg = TransformerConfig(**lm)
    params = init_transformer_params(jax.random.PRNGKey(seed), cfg)
    prompt = jnp.asarray(
        [make_prompt(job["ref_prompt_len"], cfg.vocab_size, 1)], jnp.int32)
    out = generate(params, prompt, cfg, job["ref_new_tokens"], cache=False)
    report["reference_tokens"] = np.asarray(out)[0, prompt.shape[1]:].tolist()
    del params, out

    # -- the train step, bf16, on a repeated batch
    cfg = cfg._replace(dtype=jnp.bfloat16)
    params = init_transformer_params(jax.random.PRNGKey(seed), cfg)
    velocity = init_velocity(params)
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(
        0, cfg.vocab_size, (job["batch"], job["tokens_per_row"])), jnp.int32)
    step = make_train_step(cfg)
    # lm_loss trains on tokens[:, :-1]: a (B, 1024) batch runs T=1023,
    # no 128-aligned tile divides it, and BOTH passes silently take the
    # blockwise reference. Look at the program, not at the shapes.
    kernels = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
    traced = step.trace(params, velocity, tokens)
    jaxpr = str(traced.jaxpr)
    for name in kernels:
        check(f"name={name}" in jaxpr,
              f"Pallas kernel {name!r} is not in the train step: with "
              f"tokens {tuple(tokens.shape)} the block sees "
              f"T={tokens.shape[1] - 1} and attention fell back to "
              "blockwise_attention")
    if on_tpu:
        text = traced.lower().as_text()
        check("tpu_custom_call" in text
              and all(f'kernel_name = "{n}"' in text for n in kernels),
              "the lowered train step holds no Mosaic custom call for the "
              "flash kernels")
    losses = []
    t0 = time.perf_counter()
    params, velocity, loss = step(params, velocity, tokens)
    losses.append(float(jax.block_until_ready(loss)))
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(job["steps"] - 1):
        params, velocity, loss = step(params, velocity, tokens)
        losses.append(float(loss))
    jax.block_until_ready(params)
    t_rest = time.perf_counter() - t0
    check(all(np.isfinite(losses)), f"loss is not finite: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on a repeated batch: {losses}")
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    report.update({
        "params_millions": round(n_params / 1e6, 1),
        "dtype": "bfloat16", "tokens": list(tokens.shape),
        "losses": [round(v, 4) for v in losses],
        "seconds": {"first_step_with_compile": round(t_first, 2),
                    "steady_step": round(t_rest / max(1, job["steps"] - 1),
                                         4)},
    })
    del params, velocity

    # -- four chips: the data-parallel trainers on a real mesh
    if device["count"] >= 4:
        report["four_chip"] = _four_chip(job["mlp"], job["mlp_batch"])
    else:
        report["four_chip"] = (f"not run: {device['count']} device(s), "
                               "needs 4")
    print(json.dumps({"train_report": report}), flush=True)


def _four_chip(sizes, batch: int) -> dict:
    """`DataParallelTrainer` and `ShardedUpdateTrainer` on
    `make_mesh({"data": 4})`: every device holds one batch shard, the
    score falls, and the replicated parameters agree across devices."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.config import MultiLayerConfiguration
    from deeplearning4j_tpu.datasets import ListDataSetIterator
    from deeplearning4j_tpu.datasets.api import DataSet
    from deeplearning4j_tpu.datasets.mnist import synthetic_mnist
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel import (DataParallelTrainer,
                                             ShardedUpdateTrainer,
                                             make_mesh)

    conf = mlp_conf(sizes)
    for c in conf["confs"]:
        c.update(compute_dtype="bfloat16", lr=0.05, num_iterations=1,
                 batch_size=batch)
    conf_json = json.dumps(conf)
    mesh = make_mesh({"data": 4}, devices=jax.devices()[:4])
    x, y = synthetic_mnist(batch * 4)
    x, y = np.asarray(x)[:, :sizes[0]], np.asarray(y)
    out = {}
    for cls in (DataParallelTrainer, ShardedUpdateTrainer):
        net = MultiLayerNetwork(MultiLayerConfiguration.from_json(conf_json))
        trainer = cls(net, mesh)
        data = ListDataSetIterator(DataSet(x, y), batch_size=batch)
        first = next(iter(trainer._make_feed(data, None))).features
        shards = first.addressable_shards
        check(len({s.device for s in shards}) == 4
              and all(s.data.shape[0] == batch // 4 for s in shards),
              f"{cls.__name__}: the batch is not one shard per device")
        before = float(net.score(x[:batch], y[:batch]))
        t0 = time.perf_counter()
        trainer.fit(data, epochs=2)
        jax.block_until_ready(net._params)
        seconds = time.perf_counter() - t0
        after = float(net.score(x[:batch], y[:batch]))
        check(np.isfinite(after) and after < before,
              f"{cls.__name__}: score {before} -> {after}")
        for leaf in jax.tree_util.tree_leaves(net._params):
            copies = [np.asarray(s.data) for s in leaf.addressable_shards]
            check(len(copies) == 4 and all(
                c.shape == leaf.shape and np.array_equal(c, copies[0])
                for c in copies),
                f"{cls.__name__}: parameters differ across devices")
        out[cls.__name__] = {"score": [round(before, 4), round(after, 4)],
                             "seconds_8_steps_with_compile":
                                 round(seconds, 2)}
    return out


# ----------------------------------------------------------------- main
def main() -> int:
    serve = serve_leg()
    train = train_leg()
    check(serve["device"] == train["device"],
          f"the two legs saw different devices: {serve['device']} vs "
          f"{train['device']}")
    check(serve["first_tokens"] == train["reference_tokens"],
          "cli serve (paged cache, flash prefill, Pallas decode) and the "
          "full-recompute reference disagree on the first prompt:\n"
          f"  served    {serve['first_tokens']}\n"
          f"  reference {train['reference_tokens']}")
    say("served tokens match the full-recompute reference")
    print(json.dumps({"ok": True, "device": serve["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
