"""BERT masked-LM pretraining step + sequence-classification fine-tune
over the BertIterator masking pipeline (reference dl4j BertIterator +
SameDiff BERT training; here via the native `zoo.BertModel`).

A toy vocab/corpus keeps it fast; swap in a real WordPiece vocab file and
corpus for production."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

import numpy as np

from deeplearning4j_tpu.nlp import BertIterator, BertWordPieceTokenizer
from deeplearning4j_tpu.train.updaters import Adam
from deeplearning4j_tpu.zoo import BertConfig, BertModel

VOCAB = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]",
         "the", "quick", "brown", "fox", "jumped", "over", "lazy", "dog",
         "good", "bad", "movie", "great", "terrible"]


def main():
    tok = BertWordPieceTokenizer(VOCAB)
    cfg = BertConfig(vocab_size=len(VOCAB), hidden=64, n_layers=2,
                     n_heads=4, intermediate=128, max_len=16)

    # --- masked-LM phase ---
    corpus = ["the quick brown fox jumped over the lazy dog"] * 16
    mlm_it = BertIterator(tok, corpus, batch_size=8, max_length=16,
                          task=BertIterator.TASK_UNSUPERVISED, seed=0)
    model = BertModel(cfg, updater=Adam(1e-3))
    model.fit(mlm_it, epochs=3)
    print(f"MLM loss after pretrain: {model.score():.4f}")

    # --- classification fine-tune (same encoder weights) ---
    sents = ["good great movie", "great good fox", "bad terrible movie",
             "terrible bad dog"] * 8
    labels = [1, 1, 0, 0] * 8
    cls_it = BertIterator(tok, sents, batch_size=8, max_length=16,
                          task=BertIterator.TASK_SEQ_CLASSIFICATION,
                          labels=labels, n_classes=2, seed=1)
    model.fit(cls_it, epochs=10)
    print(f"classifier loss: {model.score():.4f}")

    ids, mask = next(iter(cls_it)).features
    probs = np.asarray(model.output_cls(ids, mask))
    print("class probabilities (first 4):\n", probs[:4].round(3))


if __name__ == "__main__":
    main()
