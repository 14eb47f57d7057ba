"""Network configuration builders.

Reference: org.deeplearning4j.nn.conf.NeuralNetConfiguration.Builder →
ListBuilder → MultiLayerConfiguration. The fluent surface matches the
reference (seed/updater/weightInit/activation/l2/list/layer/setInputType/
build); build() performs the same shape-inference walk the reference's
ListBuilder does — inferring each layer's nIn from the propagated
InputType and auto-inserting input preprocessors between layer families.
"""

from __future__ import annotations

from deeplearning4j_tpu.ndarray.dtype import DataType
from deeplearning4j_tpu.nn import updaters as _upd
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf import recurrent as R
from deeplearning4j_tpu.nn.conf import preprocessors as PP


class BackpropType:
    Standard = "standard"
    TruncatedBPTT = "tbptt"


class GradientNormalization:
    NoNormalization = None
    RenormalizeL2PerLayer = "renormalize_l2_per_layer"
    RenormalizeL2PerParamType = "renormalize_l2_per_param_type"
    ClipElementWiseAbsoluteValue = "clip_elementwise"
    ClipL2PerLayer = "clip_l2_per_layer"
    ClipL2PerParamType = "clip_l2_per_param_type"


class MultiLayerConfiguration:
    def __init__(self, layers, defaults, seed, dataType, inputType,
                 preprocessors, backpropType, tbpttFwdLength, tbpttBackLength,
                 gradientNormalization=None, gradientNormalizationThreshold=1.0):
        self.layers = layers
        self.defaults = defaults
        self.seed = seed
        self.dataType = dataType
        self.inputType = inputType
        self.preprocessors = preprocessors  # {layer_index: InputPreProcessor}
        self.backpropType = backpropType
        self.tbpttFwdLength = tbpttFwdLength
        self.tbpttBackLength = tbpttBackLength
        self.gradientNormalization = gradientNormalization
        self.gradientNormalizationThreshold = gradientNormalizationThreshold
        self.activationCheckpointing = defaults.get(
            "activationCheckpointing", False)
        self.checkpointPolicy = defaults.get("checkpointPolicy")
        self.optimizationAlgo = defaults.get(
            "optimizationAlgo", "STOCHASTIC_GRADIENT_DESCENT")
        self.maxNumLineSearchIterations = defaults.get(
            "maxNumLineSearchIterations", 20)
        # resolved per-layer input types (set during shape inference)
        self.layerInputTypes = []

    def toJson(self) -> str:
        """Config-only JSON round trip (reference:
        MultiLayerConfiguration.toJson)."""
        from deeplearning4j_tpu.util import serde

        return serde.to_json(self)

    @staticmethod
    def fromJson(text: str) -> "MultiLayerConfiguration":
        from deeplearning4j_tpu.util import serde

        return serde.from_json(text, MultiLayerConfiguration)

    def inferShapes(self):
        """Propagate InputType through layers; auto-insert preprocessors.

        Mirrors MultiLayerConfiguration.Builder.build()'s use of
        getOutputType/getPreProcessorForInputType in the reference.
        """
        if self.inputType is None:
            raise ValueError(
                "setInputType(...) is required (or set nIn on every layer)")
        cur = self.inputType
        if cur.kind == InputType.CNN_FLAT:
            first = self.layers[0]
            if isinstance(first, (L.ConvolutionLayer, L.SubsamplingLayer, L.BatchNormalization)):
                # reshape flat input to CNN at the entry (reference:
                # FeedForwardToCnnPreProcessor for convolutionalFlat)
                self.preprocessors.setdefault(0, PP.FeedForwardToCnnPreProcessor(
                    cur.height, cur.width, cur.channels))
                cur = InputType.convolutional(cur.height, cur.width, cur.channels)
            else:
                cur = InputType.feedForward(cur.arrayElementsPerExample())
        self.layerInputTypes = []
        for i, layer in enumerate(self.layers):
            layer.mergeGlobals(self.defaults)
            if i in self.preprocessors:
                cur = self.preprocessors[i].getOutputType(cur)
            else:
                pp, cur2 = self._auto_preprocessor(layer, cur)
                if pp is not None:
                    self.preprocessors[i] = pp
                    cur = cur2
            if hasattr(layer, "inferNIn"):
                layer.inferNIn(cur)
            self.layerInputTypes.append(cur)
            cur = layer.getOutputType(cur)
        self.outputType = cur
        return self

    @staticmethod
    def _wants(layer):
        layer = _unwrap_layer(layer)
        if isinstance(layer, (R.BaseRecurrentLayer, R.Bidirectional, R.LastTimeStep,
                              L.RnnOutputLayer, L.Convolution1DLayer, L.EmbeddingSequenceLayer)):
            return InputType.RNN
        if isinstance(layer, (L.ConvolutionLayer, L.SubsamplingLayer, L.Upsampling2D,
                              L.ZeroPaddingLayer, L.Cropping2D, L.LocalResponseNormalization)) \
                and not isinstance(layer, L.Convolution1DLayer):
            return InputType.CNN
        if isinstance(layer, (L.DenseLayer, L.BaseOutputLayer, L.EmbeddingLayer)):
            return InputType.FF
        return None  # format-agnostic (BN, activation, dropout, global pool...)

    def _auto_preprocessor(self, layer, cur):
        return auto_preprocessor(layer, cur)


def _unwrap_layer(layer):
    """Look through delegating wrappers (MaskZeroLayer.underlying,
    FrozenLayerWithBackprop.layer, ...) for isinstance-based format and
    nIn inference."""
    seen = 0
    while seen < 8:  # cycle guard
        inner = layer.__dict__.get("underlying") or layer.__dict__.get("layer")
        if inner is None or isinstance(layer, R.Bidirectional):
            # Bidirectional declares its own RNN format; don't unwrap it
            return layer
        layer = inner
        seen += 1
    return layer


def input_type_from_first_layer(layers):
    """InputType derived from an explicit first-layer nIn when no
    setInputType(...) was given — shared by ListBuilder.build() and the
    static validator so the two can never diverge. None when the first
    layer has no nIn to derive from."""
    first = _unwrap_layer(layers[0])
    if getattr(first, "nIn", None) is None:
        return None
    return InputType.feedForward(first.nIn) \
        if not isinstance(first, (R.BaseRecurrentLayer, R.Bidirectional,
                                  L.RnnOutputLayer)) \
        else InputType.recurrent(first.nIn)


def auto_preprocessor(layer, cur):
    """Auto-insert a format preprocessor for a layer given the incoming
    InputType (shared by sequential and graph shape inference)."""
    wants = MultiLayerConfiguration._wants(layer)
    if wants is None or cur.kind == wants:
        return None, cur
    if cur.kind == InputType.CNN and wants == InputType.FF:
        pp = PP.CnnToFeedForwardPreProcessor(cur.height, cur.width, cur.channels)
        return pp, pp.getOutputType(cur)
    if cur.kind == InputType.RNN and wants == InputType.FF:
        pp = PP.RnnToFeedForwardPreProcessor()
        return pp, pp.getOutputType(cur)
    if cur.kind == InputType.FF and wants == InputType.RNN:
        pp = PP.FeedForwardToRnnPreProcessor()
        return pp, pp.getOutputType(cur)
    if cur.kind == InputType.CNN and wants == InputType.RNN:
        pp = PP.CnnToRnnPreProcessor(cur.height, cur.width, cur.channels)
        return pp, pp.getOutputType(cur)
    raise ValueError(
        f"No preprocessor for {cur.kind} -> {wants} (layer {type(layer).__name__})")


class ListBuilder:
    def __init__(self, defaults):
        self._defaults = defaults
        self._layers = []
        self._preprocessors = {}
        self._inputType = None
        self._backpropType = BackpropType.Standard
        self._tbpttFwd = self._tbpttBack = 20

    def layer(self, *args):
        """layer(l) or layer(index, l) like the reference."""
        if len(args) == 2:
            idx, l = args
            while len(self._layers) <= idx:
                self._layers.append(None)
            self._layers[idx] = l
        else:
            self._layers.append(args[0])
        return self

    def setInputType(self, it: InputType):
        self._inputType = it
        return self

    def inputPreProcessor(self, idx: int, pp):
        self._preprocessors[idx] = pp
        return self

    def backpropType(self, bp):
        self._backpropType = bp
        return self

    def tBPTTForwardLength(self, n: int):
        self._tbpttFwd = n
        return self

    def tBPTTBackwardLength(self, n: int):
        self._tbpttBack = n
        return self

    def tBPTTLength(self, n: int):
        self._tbpttFwd = self._tbpttBack = n
        return self

    def build(self) -> MultiLayerConfiguration:
        if any(l is None for l in self._layers):
            raise ValueError("Gap in layer indices")
        d = self._defaults
        conf = MultiLayerConfiguration(
            layers=self._layers,
            defaults=d,
            seed=d.get("seed", 12345),
            dataType=d.get("dataType", DataType.FLOAT),
            inputType=self._inputType,
            preprocessors=dict(self._preprocessors),
            backpropType=self._backpropType,
            tbpttFwdLength=self._tbpttFwd,
            tbpttBackLength=self._tbpttBack,
            gradientNormalization=d.get("gradientNormalization"),
            gradientNormalizationThreshold=d.get("gradientNormalizationThreshold", 1.0),
        )
        if self._inputType is not None:
            conf.inferShapes()
        else:
            # all nIn set explicitly: derive input type from first layer
            # (looking through wrapper layers for both nIn and format)
            conf.inputType = input_type_from_first_layer(self._layers)
            if conf.inputType is None:
                raise ValueError("Either setInputType(...) or nIn on the first layer")
            conf.inferShapes()
        return conf


class NeuralNetConfiguration:
    class Builder:
        def __init__(self):
            self._d = {}

        # fluent setters, mirroring the reference builder
        def optimizationAlgo(self, algo):
            """Reference: NeuralNetConfiguration.Builder.optimizationAlgo
            (OptimizationAlgorithm enum): STOCHASTIC_GRADIENT_DESCENT
            (default, per-layer updaters), LINE_GRADIENT_DESCENT,
            CONJUGATE_GRADIENT, or LBFGS (nn/solvers.py — whole-pytree
            optax step with jitted line search)."""
            from deeplearning4j_tpu.nn.solvers import OptimizationAlgorithm

            self._d["optimizationAlgo"] = OptimizationAlgorithm.resolve(algo)
            return self

        def maxNumLineSearchIterations(self, n):
            """Line-search iteration cap for the non-SGD algorithms
            (reference: Builder.maxNumLineSearchIterations)."""
            self._d["maxNumLineSearchIterations"] = int(n)
            return self

        def seed(self, s):
            self._d["seed"] = int(s)
            return self

        def updater(self, u):
            self._d["updater"] = _upd.resolve(u) if not isinstance(u, _upd.IUpdater) else u
            return self

        def checkpointPolicy(self, policy):
            """Named rematerialization policy for the whole train step
            (jax.checkpoint with save_only_these_names). Currently:

            - "save_conv_outputs": save ONLY conv/dense (MXU) outputs as
              backward residuals; recompute the elementwise tails
              (BN/activation/add) from them during the backward pass.
              On bandwidth-bound steps this trades cheap recompute FLOPs
              for the write+read of every elementwise intermediate.
            - None: store whatever autodiff needs (default).

            Differs from activationCheckpointing (per-layer remat, a
            capacity lever): this is a BANDWIDTH lever with a policy
            boundary around the whole loss. ComputationGraph only."""
            if policy not in (None, "save_conv_outputs"):
                raise ValueError(f"unknown checkpointPolicy {policy!r}")
            self._d["checkpointPolicy"] = policy
            return self

        def activationCheckpointing(self, flag=True):
            """Rematerialize layer activations in the backward pass
            (jax.checkpoint): activations are recomputed instead of
            stored, trading ~1 extra forward of FLOPs for O(depth) ->
            O(1) activation memory. TPU-first feature (no upstream
            analog; the reference's workspaces manage allocator reuse,
            not recomputation). Most useful for deep nets / long
            sequences that overflow HBM."""
            self._d["activationCheckpointing"] = bool(flag)
            return self

        def biasUpdater(self, u):
            self._d["biasUpdater"] = u
            return self

        def weightInit(self, w):
            self._d["weightInit"] = w
            return self

        def dist(self, distribution):
            self._d["distribution"] = distribution
            self._d["weightInit"] = "distribution"
            return self

        def activation(self, a):
            self._d["activation"] = a
            return self

        def l1(self, v):
            self._d["l1"] = float(v)
            return self

        def l2(self, v):
            self._d["l2"] = float(v)
            return self

        def l1Bias(self, v):
            self._d["l1Bias"] = float(v)
            return self

        def l2Bias(self, v):
            self._d["l2Bias"] = float(v)
            return self

        def weightDecay(self, v):
            self._d["weightDecay"] = float(v)
            return self

        def dropOut(self, v):
            # float (retain prob) or an nn.conf.dropout.IDropout strategy
            self._d["dropOut"] = v if not isinstance(v, (int, float)) else float(v)
            return self

        def weightNoise(self, wn):
            """Per-step weight perturbation during training (reference:
            NeuralNetConfiguration.Builder.weightNoise — DropConnect or
            WeightNoise from nn.conf.weightnoise)."""
            self._d["weightNoise"] = wn
            return self

        def _add_constraints(self, constraints, weights, biases):
            import copy

            # configured COPIES: mutating the caller's instances would
            # corrupt a constraint object shared between builders
            cs = []
            for c in constraints:
                c = copy.copy(c)
                c.applyToWeights, c.applyToBiases = weights, biases
                cs.append(c)
            self._d["constraints"] = (self._d.get("constraints") or []) + cs
            return self

        def constrainWeights(self, *constraints):
            """Apply constraints to every layer's weights after each update
            (reference: NeuralNetConfiguration.Builder.constrainWeights)."""
            return self._add_constraints(constraints, True, False)

        def constrainBias(self, *constraints):
            return self._add_constraints(constraints, False, True)

        def constrainAllParameters(self, *constraints):
            return self._add_constraints(constraints, True, True)

        def dataType(self, dt):
            self._d["dataType"] = DataType.from_dtype(dt) if not isinstance(dt, DataType) else dt
            return self

        def gradientNormalization(self, gn):
            self._d["gradientNormalization"] = gn
            return self

        def gradientNormalizationThreshold(self, t):
            self._d["gradientNormalizationThreshold"] = float(t)
            return self

        def convolutionMode(self, m):
            self._d["convolutionMode"] = m
            return self

        def miniBatch(self, flag):
            self._d["miniBatch"] = bool(flag)
            return self

        def trainingWorkspaceMode(self, *_):
            return self  # workspaces are XLA's job; accepted for parity

        def inferenceWorkspaceMode(self, *_):
            return self

        def cudnnAlgoMode(self, *_):
            return self  # no cuDNN on TPU; accepted for parity

        def list(self) -> ListBuilder:
            return ListBuilder(dict(self._d))

        def graphBuilder(self):
            try:
                from deeplearning4j_tpu.nn.conf.graph import GraphBuilder
            except ImportError as e:
                raise NotImplementedError(
                    "ComputationGraph configuration (nn.conf.graph) is not "
                    "available in this build; use .list() for sequential "
                    "networks") from e
            return GraphBuilder(dict(self._d))
