"""Word2Vec skip-gram + nearest neighbors + the moving-window
classification bridge (reference Word2Vec + Word2VecDataSetIterator)."""
from deeplearning4j_tpu.nlp import (LabelAwareSentenceIterator, Word2Vec,
                                    Word2VecDataSetIterator)
from deeplearning4j_tpu.utils import jaxenv

jaxenv.configure()  # compile cache + platform pin, before JAX starts

corpus = ["the cat sat on the mat", "the dog sat on the rug",
          "the cat and the dog play in the yard",
          "the king wears the crown in the castle",
          "the queen wears the crown in the castle",
          "a royal king and a royal queen sit on the throne"] * 40

w2v = Word2Vec(corpus, layer_size=32, window=3, min_word_frequency=3,
               learning_rate=0.1, negative=5, batch_pairs=256,
               iterations=40, seed=7).fit()
print("nearest to 'king':", w2v.words_nearest("king", n=3))
print("king~queen:", round(w2v.similarity("king", "queen"), 3),
      " king~cat:", round(w2v.similarity("king", "cat"), 3))

it = Word2VecDataSetIterator(
    w2v,
    LabelAwareSentenceIterator([("animals", "the cat sat on the mat"),
                                ("royalty", "the king wears the crown")]),
    labels=["animals", "royalty"], batch=16)
ds = it.next()
print("window batch:", ds.features.shape, "->", ds.labels.shape)
