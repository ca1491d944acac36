"""Downstream fine-tuning: span-F1 metrics, label alignment, and tiny
end-to-end NER/NCC runs (the reference's train_ner.py / train_ncc.py
capabilities on synthetic Bengali-shaped data)."""
import numpy as np
import pytest

from dedloc_tpu.finetune.driver import EarlyStopping, FinetuneArguments
from dedloc_tpu.finetune.metrics import (
    accuracy_score,
    align_labels_with_words,
    extract_entities,
    span_f1,
)
from dedloc_tpu.finetune.ner import WIKIANN_LABELS, encode_ner_examples, run_ner
from dedloc_tpu.finetune.ncc import encode_ncc_examples, run_ncc
from dedloc_tpu.models.albert import AlbertConfig


def test_extract_entities_bio():
    tags = ["O", "B-PER", "I-PER", "O", "B-LOC", "B-ORG", "I-ORG"]
    assert extract_entities(tags) == {
        ("PER", 1, 3),
        ("LOC", 4, 5),
        ("ORG", 5, 7),
    }


def test_extract_entities_orphan_continuation():
    # bare I-X opens a span (seqeval lenient default); type switch closes it
    assert extract_entities(["I-PER", "I-LOC"]) == {("PER", 0, 1), ("LOC", 1, 2)}
    assert extract_entities(["B-PER", "I-PER", "I-PER"]) == {("PER", 0, 3)}


def test_span_f1_perfect_and_partial():
    ref = [["B-PER", "I-PER", "O"]]
    assert span_f1(ref, ref)["f1"] == 1.0
    m = span_f1([["B-PER", "O", "O"]], ref)
    assert m["precision"] == 0.0 and m["recall"] == 0.0
    assert m["accuracy"] == pytest.approx(2 / 3)


def test_align_labels_with_words():
    # word_ids for "[CLS] to k1 k2 [SEP]" where word 1 has two sub-tokens
    word_ids = [None, 0, 1, 1, None]
    labels = align_labels_with_words(word_ids, [3, 5])
    assert labels == [-100, 3, 5, -100, -100]
    labels_all = align_labels_with_words(word_ids, [3, 5], label_all_tokens=True)
    assert labels_all == [-100, 3, 5, 5, -100]


def test_accuracy_score():
    assert accuracy_score([1, 2, 3], [1, 2, 0]) == pytest.approx(2 / 3)


def test_early_stopping_patience():
    s = EarlyStopping(patience=2, threshold=0.0, greater_is_better=False)
    assert not s.record(1.0)
    assert not s.record(0.9)
    assert not s.record(0.95)  # worse: bad_evals=1
    assert s.record(0.92)  # worse again: stop
    assert s.best == 0.9


def _fake_word_tokenizer(words):
    """Deterministic sub-word splitter: word i -> 1 + (len(word) > 3) tokens."""
    ids, word_ids = [2], [None]  # [CLS]
    for wi, w in enumerate(words):
        n = 2 if len(w) > 3 else 1
        for _ in range(n):
            ids.append(5 + (hash(w) % 100))
            word_ids.append(wi)
    ids.append(3)  # [SEP]
    word_ids.append(None)
    return {"input_ids": ids, "word_ids": word_ids}


def _ner_examples(n, rng):
    examples = []
    for _ in range(n):
        length = rng.integers(3, 7)
        words = [f"w{rng.integers(0, 30)}" + "x" * rng.integers(0, 4) for _ in range(length)]
        tags = []
        i = 0
        while i < length:
            if rng.random() < 0.3:
                tags.append(1)  # B-PER
                if i + 1 < length and rng.random() < 0.5:
                    tags.append(2)  # I-PER
                    i += 2
                    continue
            else:
                tags.append(0)
            i += 1
        examples.append({"tokens": words, "ner_tags": tags[:length]})
    return examples


def test_encode_ner_examples_shapes(rng):
    examples = _ner_examples(4, rng)
    data = encode_ner_examples(examples, _fake_word_tokenizer, max_seq_length=32)
    assert data["input_ids"].shape == (4, 32)
    assert data["labels"].shape == (4, 32)
    # CLS position is always ignored; padding is ignored
    assert (data["labels"][:, 0] == -100).all()
    assert ((data["labels"] != -100) <= (data["attention_mask"] > 0)).all()


def test_run_ner_end_to_end(rng):
    from dedloc_tpu.finetune.ner import NerArguments

    args = NerArguments(
        max_seq_length=32,
        train=FinetuneArguments(
            num_train_epochs=2,
            per_device_batch_size=4,
            learning_rate=1e-3,
            early_stopping_patience=3,
        ),
    )
    cfg = AlbertConfig.tiny(vocab_size=128, max_position_embeddings=32)
    params, history = run_ner(
        args,
        cfg,
        _ner_examples(12, rng),
        _ner_examples(6, rng),
        _fake_word_tokenizer,
    )
    assert len(history) >= 1
    assert np.isfinite(history[-1]["eval_loss"])
    assert "eval_f1" in history[-1]


def test_run_ncc_end_to_end(rng):
    from dedloc_tpu.finetune.ncc import NccArguments

    def tokenize_text(text):
        return [2] + [5 + (ord(c) % 50) for c in text[:20]] + [3]

    examples = [
        {"text": f"news story {i} " + "ab" * (i % 5), "label": i % 3}
        for i in range(16)
    ]
    args = NccArguments(
        max_seq_length=24,
        train=FinetuneArguments(
            num_train_epochs=2, per_device_batch_size=4, learning_rate=1e-3
        ),
    )
    cfg = AlbertConfig.tiny(vocab_size=128, max_position_embeddings=24)
    params, history = run_ncc(
        args, cfg, examples[:12], examples[12:], tokenize_text,
        label_list=["a", "b", "c"],
    )
    assert len(history) >= 1
    assert 0.0 <= history[-1]["eval_accuracy"] <= 1.0


def test_finetune_warm_start_uses_pretrained_backbone(rng):
    """init_params['albert'] must be carried into the fine-tuned params."""
    import jax
    import jax.numpy as jnp

    from dedloc_tpu.finetune.driver import finetune
    from dedloc_tpu.models.albert import AlbertForSequenceClassification

    cfg = AlbertConfig.tiny(vocab_size=64, max_position_embeddings=16)
    model = AlbertForSequenceClassification(cfg, num_labels=2)
    ids = jnp.zeros((2, 16), jnp.int32)
    pre = model.init(jax.random.PRNGKey(7), ids)["params"]
    marker = jax.tree_util.tree_map(lambda x: x * 0 + 0.123, pre["albert"])

    data = {
        "input_ids": np.ones((4, 16), np.int32),
        "attention_mask": np.ones((4, 16), np.int32),
        "labels": np.array([0, 1, 0, 1], np.int32),
    }
    args = FinetuneArguments(num_train_epochs=0, per_device_batch_size=4)
    best, _ = finetune(model, {"albert": marker}, data, data, args)
    leaf = jax.tree_util.tree_leaves(best["albert"])[0]
    assert np.allclose(np.asarray(leaf), 0.123)


def _write_jsonl(path, rows):
    import json

    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


def _tiny_tokenizer_file(tmp_path):
    from dedloc_tpu.data.tokenizer import FastTokenizer, train_unigram_tokenizer

    corpus = [
        "kolkata news story about sports",
        "national desk reports state politics",
        "entertainment world update international",
    ] * 4
    tok = FastTokenizer(train_unigram_tokenizer(corpus, vocab_size=200))
    path = str(tmp_path / "tokenizer.json")
    tok.save(path)
    return path


def test_ner_main_real_datasets_path(tmp_path, rng):
    """Drive the NER CLI main end-to-end through the genuine
    ``datasets.load_dataset`` ingestion (local data-files dir — the same
    Arrow path the networked wikiann/bn fetch takes, train_ner.py)."""
    from dedloc_tpu.finetune import ner

    ds_dir = tmp_path / "wikiann_local"
    ds_dir.mkdir()
    rows = [
        {"tokens": ["kolkata", "reports", "sports"], "ner_tags": [5, 0, 0]},
        {"tokens": ["national", "desk"], "ner_tags": [3, 4]},
        {"tokens": ["state", "politics", "update"], "ner_tags": [0, 0, 0]},
        {"tokens": ["world", "news"], "ner_tags": [1, 2]},
    ]
    _write_jsonl(ds_dir / "train.jsonl", rows * 3)
    _write_jsonl(ds_dir / "validation.jsonl", rows)

    ner.main([
        "--dataset_name", str(ds_dir),
        "--model_size", "tiny",
        "--max_seq_length", "32",
        "--tokenizer_path", _tiny_tokenizer_file(tmp_path),
        "--train.num_train_epochs", "1",
        "--train.per_device_batch_size", "4",
        "--train.learning_rate", "1e-3",
    ])


def test_ncc_main_real_datasets_path(tmp_path):
    """Same for the NCC CLI (indic_glue sna.bn shape: text + label)."""
    from dedloc_tpu.finetune import ncc

    ds_dir = tmp_path / "sna_local"
    ds_dir.mkdir()
    rows = [
        {"text": "kolkata news story about sports", "label": 4},
        {"text": "national desk reports state politics", "label": 2},
        {"text": "entertainment world update", "label": 5},
        {"text": "international desk update", "label": 3},
    ]
    _write_jsonl(ds_dir / "train.jsonl", rows * 3)
    _write_jsonl(ds_dir / "validation.jsonl", rows)

    ncc.main([
        "--dataset_name", str(ds_dir),
        "--model_size", "tiny",
        "--max_seq_length", "24",
        "--tokenizer_path", _tiny_tokenizer_file(tmp_path),
        "--train.num_train_epochs", "1",
        "--train.per_device_batch_size", "4",
        "--train.learning_rate", "1e-3",
    ])


def test_finetune_warm_start_rejects_shape_mismatch():
    """A checkpoint whose backbone doesn't match the config (e.g. a smaller
    position table than --max_seq_length needs) must error, not silently
    clamp positions under jit."""
    import jax

    from dedloc_tpu.finetune.driver import finetune
    from dedloc_tpu.models.albert import AlbertForSequenceClassification

    small = AlbertConfig.tiny(vocab_size=64, max_position_embeddings=16)
    ckpt_model = AlbertForSequenceClassification(small, num_labels=2)
    ckpt_params = ckpt_model.init(
        jax.random.PRNGKey(0), np.zeros((1, 16), np.int32)
    )["params"]

    grown = AlbertConfig.tiny(vocab_size=64, max_position_embeddings=32)
    model = AlbertForSequenceClassification(grown, num_labels=2)
    data = {
        "input_ids": np.ones((4, 32), np.int32),
        "attention_mask": np.ones((4, 32), np.int32),
        "labels": np.array([0, 1, 0, 1], np.int32),
    }
    args = FinetuneArguments(num_train_epochs=0, per_device_batch_size=4)
    with pytest.raises(ValueError, match="position table|model config"):
        finetune(model, {"albert": ckpt_params["albert"]}, data, data, args)


def test_model_size_resolver_is_strict():
    from dedloc_tpu.models.albert import AlbertConfig as C

    assert C.named("tiny") is C.tiny and C.named("large") is C.large
    with pytest.raises(ValueError, match="unknown model_size"):
        C.named("larg")
    with pytest.raises(ValueError, match="unknown model_size"):
        C.named("vocab_size")  # class attribute, but not a size


def test_encode_truncation_preserves_sep():
    from dedloc_tpu.finetune.ncc import encode_ncc_examples
    from dedloc_tpu.finetune.ner import encode_ner_examples

    SEP = 3
    # NCC: 10 tokens into max_seq 6 -> last kept position rewritten to [SEP]
    data = encode_ncc_examples(
        [{"text": "x", "label": 1}],
        lambda text: [2, 10, 11, 12, 13, 14, 15, 16, 17, SEP],
        max_seq_length=6,
        sep_token_id=SEP,
    )
    assert data["input_ids"][0, 5] == SEP
    assert data["attention_mask"][0].sum() == 6

    # NER: truncated tail becomes [SEP] with label -100
    enc = {"input_ids": [2, 10, 11, 12, 13, SEP],
           "word_ids": [None, 0, 1, 2, 3, None]}
    data = encode_ner_examples(
        [{"tokens": ["a", "b", "c", "d"], "ner_tags": [1, 2, 3, 4]}],
        lambda words: enc,
        max_seq_length=4,
        sep_token_id=SEP,
    )
    assert data["input_ids"][0, 3] == SEP
    assert data["labels"][0, 3] == -100
