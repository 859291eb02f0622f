"""Word2Vec on a text corpus (dl4j-examples Word2VecRawTextExample;
BASELINE.md config #4): build vocab, train skip-gram, query nearest words.

Run: python examples/word2vec_basic.py [path/to/corpus.txt]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deeplearning4j_tpu.nlp.tokenization import DefaultTokenizerFactory
from deeplearning4j_tpu.nlp.word2vec import Word2Vec

FALLBACK = ("day night sun moon light dark warm cold fire ice "
            "king queen man woman boy girl prince princess ") * 500


def main():
    text = open(sys.argv[1]).read() if len(sys.argv) > 1 else FALLBACK
    tok = DefaultTokenizerFactory()
    sents = [tok.create(line).get_tokens()
             for line in text.splitlines() if line.strip()] or \
            [tok.create(text).get_tokens()]
    w2v = (Word2Vec.Builder()
           .layer_size(100).window_size(5).min_word_frequency(2)
           .negative_sample(5).epochs(3).seed(42).build())
    w2v.fit(sents)
    for probe in ("day", "king"):
        if w2v.vocab and probe in w2v.vocab:
            print(probe, "->", w2v.words_nearest(probe, 5))


if __name__ == "__main__":
    from deeplearning4j_tpu.ops.platform import configure_compilation_cache
    configure_compilation_cache()
    main()
