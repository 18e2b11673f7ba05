"""A stub engine for the toy block-filling family (`families/blockfill.py`):
the program has no engine that fills blocks by denoising, and the harness's
tests of the check need one. It has what `check.py` asks of an engine
(`config`, `params`, `generate`, `submit`), fills blocks greedily by
confidence through the family's own `BlockPasses`, finishes the block it
began (so it may answer up to B - 1 tokens past `max_new_tokens`), and
reports with its tokens the denoise step of its block that fixed each
(`fixed_in`). Its knobs are the faults the check has to catch."""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np


class BlockFillEngine:
    def __init__(self, family, spec: dict, name: str, *, choose: str = "most",
                 denoise_mask: str = "block_causal", cache_dtype: str = "bfloat16",
                 replace_token: int | None = None) -> None:
        self.config = family.model_config(spec, name)
        self.params = family.make_params(self.config, int(spec["weights"]["seed"]))
        self.cache_dtype, self.denoise_mask = cache_dtype, denoise_mask
        self._choose, self._replace, self._confidence = choose, replace_token, family.choice_score
        self._passes = family.BlockPasses(
            self.config, self.config.max_seq_len, cache_dtype, denoise_mask)

    def generate(self, prompt, options, timeout=None) -> SimpleNamespace:
        config, run = self.config, self._passes
        b, mask = config.block_length, config.mask_id
        tokens, fixed_in = list(prompt), []
        start = len(tokens) // b * b
        cache = run.prefill(self.params, tokens[:start])
        while len(fixed_in) < options.max_new_tokens:
            block = tokens[start:] + [mask] * (start + b - len(tokens))
            step_of = [-1] * (len(tokens) - start) + [None] * (start + b - len(tokens))
            for step, count in enumerate(config.schedule):
                still_open = [i for i in range(b) if step_of[i] is None]
                if not still_open:
                    break
                logits = np.array(run.denoise(self.params, block, start, cache))
                logits[:, mask] = -np.inf  # the mask id is never an answer
                confidence = np.asarray(self._confidence(logits))
                order = sorted(still_open, key=lambda i: confidence[i],
                               reverse=self._choose == "most")
                for i in order[:count]:
                    block[i], step_of[i] = int(np.argmax(logits[i])), step
            cache = run.commit(self.params, block, start, cache)
            fixed_in += [s for s in step_of if s != -1]
            tokens, start = tokens[:start] + block, start + b
        answer = tokens[len(prompt):]
        if self._replace is not None:  # altered after the passes that chose it
            answer[self._replace] = (answer[self._replace] + 97) % mask
        return SimpleNamespace(tokens=answer, fixed_in=fixed_in, finish_reason="length")

    def submit(self, request) -> SimpleNamespace:
        return SimpleNamespace(
            result=lambda timeout=None: self.generate(request.prompt_tokens, request.options))
