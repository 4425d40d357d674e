"""HFTokenizerAdapter (tokenizers + jinja2) held to transformers' AutoTokenizer.

Since PR 40 the serving tokenizer reads tokenizer.json with `tokenizers` and
renders its chat template with jinja2, so set-up no longer imports
transformers and torch. These tests pin that the ids and the text are still
transformers' own, on the committed assets/bpe4k fixture: over the prompts
the scheduler really renders (PromptEngine over synthetic clusters and
their pods) and over seeded random strings, and id by id over the whole
vocabulary. The import guard keeps the import chain from coming back.
"""

import json
import random
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
FIXTURE = str(REPO / "k8s_llm_scheduler_tpu" / "assets" / "bpe4k")


@pytest.fixture(scope="module")
def adapter():
    from k8s_llm_scheduler_tpu.engine.tokenizer import HFTokenizerAdapter

    return HFTokenizerAdapter(FIXTURE)


@pytest.fixture(scope="module")
def reference():
    transformers = pytest.importorskip("transformers")
    return transformers.AutoTokenizer.from_pretrained(FIXTURE, local_files_only=True)


def _render(reference, system: str, user: str) -> str:
    messages = [{"role": "system", "content": system}, {"role": "user", "content": user}]
    return reference.apply_chat_template(messages, add_generation_prompt=True, tokenize=False)


def _reference_parts(reference, system: str, user_prefix: str, user_suffix: str):
    """The split the adapter made when it rendered through transformers."""
    rendered = _render(reference, system, user_prefix + user_suffix)
    split_at = rendered.rfind(user_prefix) + len(user_prefix)
    assert rendered.startswith(user_suffix, split_at)
    return (reference.encode(rendered[:split_at], add_special_tokens=False),
            reference.encode(rendered[split_at:], add_special_tokens=False))


def _assert_same(adapter, reference, system: str, user: str) -> None:
    rendered = _render(reference, system, user)
    assert adapter._render(system, user) == rendered
    ids = reference.encode(rendered, add_special_tokens=False)
    assert adapter.encode(rendered) == ids
    messages = [{"role": "system", "content": system}, {"role": "user", "content": user}]
    assert adapter.chat_prompt(system, user) == ids == reference.apply_chat_template(
        messages, add_generation_prompt=True)
    assert adapter.decode(ids) == reference.decode(ids, skip_special_tokens=True)
    plain = reference.encode(user, add_special_tokens=False)
    assert adapter.encode(user) == plain
    assert adapter.decode(plain) == reference.decode(plain, skip_special_tokens=True)


@pytest.mark.parametrize("n_nodes", [3, 20, 128])
def test_scheduler_prompts_match_transformers(adapter, reference, n_nodes):
    from k8s_llm_scheduler_tpu.cluster.interface import raw_pod_to_spec
    from k8s_llm_scheduler_tpu.core.prompt import PromptEngine
    from k8s_llm_scheduler_tpu.testing import pod_burst, synthetic_cluster

    engine = PromptEngine()
    nodes = list(synthetic_cluster(n_nodes).get_node_metrics())
    adapter._prefix_encode_memo.clear()
    for raw in pod_burst(8):
        pod = raw_pod_to_spec(raw)
        cluster, tail = engine.split_prompt(pod, nodes)
        _assert_same(adapter, reference, engine.system_prompt, cluster + tail)
        # the second pod onward takes the prefix from the memo
        assert adapter.chat_prompt_parts(engine.system_prompt, cluster, tail) == (
            _reference_parts(reference, engine.system_prompt, cluster, tail))


_ALPHABET = (
    "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789"
    " \n\t  .,:;!?'\"{}[]()<>|/\\-_=+*%$#@&~`^" "éüßñ日本語한국어🙂​"
)
_SPECIALS = ["<|eot_id|>", "<|pad|>", "<|begin_of_text|>", "<|start_header_id|>",
             "<|reserved_special_1|>", " n't", " 's", " ."]


def _random_text(rng: random.Random) -> str:
    parts = []
    for _ in range(rng.randint(0, 12)):
        if rng.random() < 0.1:
            parts.append(rng.choice(_SPECIALS))
        else:
            parts.append("".join(rng.choice(_ALPHABET) for _ in range(rng.randint(0, 24))))
    return "".join(parts)


@pytest.mark.parametrize("seed", range(4))
def test_random_strings_match_transformers(adapter, reference, seed):
    rng = random.Random(1_000_003 * seed + 40)
    for _ in range(80):
        system, user_prefix, user_suffix = (_random_text(rng) for _ in range(3))
        _assert_same(adapter, reference, system, user_prefix + user_suffix)
        if user_prefix and user_suffix:
            rendered = _render(reference, system, user_prefix + user_suffix)
            pos = rendered.rfind(user_prefix)
            if pos > 0 and rendered.startswith(user_suffix, pos + len(user_prefix)):
                assert adapter.chat_prompt_parts(system, user_prefix, user_suffix) == (
                    _reference_parts(reference, system, user_prefix, user_suffix))


def test_vocabulary_and_sentinels_match_transformers(adapter, reference):
    assert adapter.vocab_size == len(reference) == 1280
    assert adapter.eos_id == reference.eos_token_id
    assert adapter.pad_id == reference.pad_token_id


@pytest.mark.parametrize("skip_special_tokens", [True, False])
def test_every_id_decodes_as_transformers_does(adapter, reference, skip_special_tokens):
    for i in range(adapter.vocab_size):
        want = reference.decode([i], skip_special_tokens=skip_special_tokens)
        if skip_special_tokens:
            assert adapter.decode([i]) == want, i
        assert adapter._tok.decode([i], skip_special_tokens=skip_special_tokens) == want, i


def test_directory_without_tokenizer_json_raises(tmp_path):
    from k8s_llm_scheduler_tpu.engine.tokenizer import HFTokenizerAdapter

    (tmp_path / "tokenizer_config.json").write_text(json.dumps({"eos_token": "</s>"}))
    with pytest.raises(FileNotFoundError, match="tokenizer.json") as info:
        HFTokenizerAdapter(str(tmp_path))
    assert str(tmp_path) in str(info.value)


def test_adapter_imports_neither_transformers_nor_torch():
    """The ~8 s (CPU) import chain stays out of set-up: a fresh process
    builds the adapter and serves a prompt without either module."""
    code = (
        "import sys\n"
        "from k8s_llm_scheduler_tpu.engine.tokenizer import HFTokenizerAdapter\n"
        f"tok = HFTokenizerAdapter({FIXTURE!r})\n"
        "assert tok.decode(tok.chat_prompt('sys', 'Node: node-1'))\n"
        "print(sorted(m for m in ('transformers', 'torch') if m in sys.modules))\n"
    )
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, capture_output=True,
                         text=True, timeout=60, check=True)
    assert out.stdout.strip() == "[]", out.stdout + out.stderr
