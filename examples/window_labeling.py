"""Moving-window classification with native annotators, end-to-end.

The reference assembled this pipeline from UIMA glue: ContextLabel span
markup (+ ContextLabelRetriever), PoStagger (OpenNLP maxent behind a
UIMA AnalysisEngine), and SWN3 sentiment scoring. Here the same
capabilities are native framework pieces: `string_with_labels` strips
the span markup, `HmmPosTagger` (trained closed-form, decoded with the
shared Viterbi scan) tags tokens, `SentimentLexicon` scores windows,
and `annotate_windows` fuses them into labeled windows whose word2vec
feature rows train a MultiLayerNetwork classifier.
"""
import numpy as np

from deeplearning4j_tpu.config import NeuralNetConfiguration
from deeplearning4j_tpu.eval import Evaluation
from deeplearning4j_tpu.nlp import Word2Vec
from deeplearning4j_tpu.nlp.pos import HmmPosTagger
from deeplearning4j_tpu.nlp.sentiment import SentimentLexicon
from deeplearning4j_tpu.nlp.windows import (annotate_windows,
                                            string_with_labels,
                                            window_as_vector)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.utils import jaxenv

jaxenv.configure()  # compile cache + platform pin, before JAX starts

# 1. span-labeled corpus (ContextLabel markup): the task is labeling
#    each window as describing an ANIMAL or ROYAL context
MARKED = [
    "the <ANIMAL> cat </ANIMAL> sat on the mat",
    "a <ANIMAL> dog </ANIMAL> slept by the door",
    "the <ANIMAL> bird </ANIMAL> sang in the tree",
    "the <ROYAL> king </ROYAL> wears the crown",
    "a <ROYAL> queen </ROYAL> rules the castle",
    "the <ROYAL> prince </ROYAL> rode to the castle",
] * 20

sentences, all_spans = [], []
for m in MARKED:
    toks, spans = string_with_labels(m)
    sentences.append(toks)
    all_spans.append(spans)
print("stripped:", sentences[0], "spans:", all_spans[0])

# 2. native PoS tagger trained on a mini tagged corpus
TAGGED = [
    [("the", "DT"), ("cat", "NN"), ("sat", "VB"), ("on", "IN"),
     ("the", "DT"), ("mat", "NN")],
    [("a", "DT"), ("dog", "NN"), ("slept", "VB"), ("by", "IN"),
     ("the", "DT"), ("door", "NN")],
    [("the", "DT"), ("king", "NN"), ("wears", "VB"), ("the", "DT"),
     ("crown", "NN")],
    [("a", "DT"), ("queen", "NN"), ("rules", "VB"), ("the", "DT"),
     ("castle", "NN")],
]
tagger = HmmPosTagger().train(TAGGED)
print("tagged:", tagger.tag_sentence(["the", "bird", "sat", "on",
                                      "the", "castle"]))

# 3. sentiment lexicon (SWN3 role) for unlabeled windows
lexicon = SentimentLexicon({"sang": 0.4, "rules": 0.3, "slept": -0.1})

# 4. word vectors for the window featurization
flat = [" ".join(s) for s in sentences]
w2v = Word2Vec(flat, layer_size=16, window=3, min_word_frequency=1,
               learning_rate=0.1, negative=5, batch_pairs=128,
               iterations=20, seed=3).fit()

# 5. labeled windows -> example matrix -> MLP classifier
WINDOW = 3
X, y, classes = [], [], ["NONE", "ANIMAL", "ROYAL"]
for toks, spans in zip(sentences, all_spans):
    for w in annotate_windows(toks, WINDOW, tagger=tagger,
                              lexicon=None, span_labels=spans):
        X.append(window_as_vector(w, w2v))
        y.append(classes.index(w.label) if w.label in classes else 0)
X = np.stack(X)
labels = np.eye(len(classes), dtype=np.float32)[y]
print("window dataset:", X.shape, "->", labels.shape)

conf = (NeuralNetConfiguration.builder()
        .lr(0.2).n_in(X.shape[1]).activation_function("tanh")
        .optimization_algo("iteration_gradient_descent")
        .num_iterations(800).use_adagrad(False)
        .list(2).hidden_layer_sizes([64])
        .override(1, layer="output", loss_function="mcxent",
                  activation_function="softmax", n_out=len(classes))
        .pretrain(False).build())
net = MultiLayerNetwork(conf)
net.fit(X, labels)
ev = Evaluation()
ev.eval(labels, np.asarray(net.output(X)))
acc = ev.accuracy()
print(f"window-label train accuracy: {acc:.3f}")
assert acc > 0.9, f"window classifier failed to fit: {acc}"

# 6. sentiment labels where no span annotation exists
for w in annotate_windows(sentences[2], WINDOW, lexicon=lexicon)[:3]:
    print("sentiment window:", w.focus_word(), "->", w.label)
