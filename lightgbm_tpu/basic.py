"""Public ``Dataset`` / ``Booster`` API
(reference: python-package/lightgbm/basic.py:712,1666).

The reference wraps the C library through ctypes; here ``Dataset`` wraps the
host-side ``BinnedDataset`` construction and ``Booster`` drives the
device-resident boosting engine directly.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from . import obs
from .config import Config
from .io.dataset import BinnedDataset
from .utils import log
from .utils.log import LightGBMError


def _is_scipy_sparse(data) -> bool:
    return hasattr(data, "toarray") and hasattr(data, "tocsr")


def _to_matrix(data) -> np.ndarray:
    """Accept numpy arrays, lists, pandas DataFrames, scipy sparse."""
    if hasattr(data, "values") and hasattr(data, "columns"):  # pandas
        return np.ascontiguousarray(data.values, dtype=np.float64)
    if _is_scipy_sparse(data):
        return np.ascontiguousarray(data.toarray(), dtype=np.float64)
    arr = np.asarray(data)
    if arr.ndim != 2:
        raise LightGBMError("Data should be 2-D")
    return np.ascontiguousarray(arr, dtype=np.float64)


def _feature_names_of(data) -> Optional[List[str]]:
    if hasattr(data, "columns"):
        return [str(c) for c in data.columns]
    return None


def _is_pandas_df(data) -> bool:
    return hasattr(data, "columns") and hasattr(data, "dtypes")


def _data_from_pandas(df, categorical_feature="auto",
                      pandas_categorical=None):
    """DataFrame -> (f64 matrix, names, categorical_feature,
    pandas_categorical).  category-dtype columns become their integer
    codes with NaN for missing; at predict/valid time the codes are
    aligned to the TRAIN-time category lists so the same string maps to
    the same code (reference: basic.py:313-354 _data_from_pandas)."""
    import pandas as pd
    cat_cols = [c for c in df.columns
                if isinstance(df[c].dtype, pd.CategoricalDtype)]
    unordered = [c for c in cat_cols if not df[c].cat.ordered]
    if cat_cols:
        df = df.copy()  # one copy covers both mutation passes below
    if pandas_categorical is None:  # train dataset defines the mapping
        pandas_categorical = [list(df[c].cat.categories) for c in cat_cols]
    else:
        if len(cat_cols) != len(pandas_categorical):
            raise LightGBMError("train and valid dataset "
                                "categorical_feature do not match.")
        for c, cats in zip(cat_cols, pandas_categorical):
            if list(df[c].cat.categories) != list(cats):
                df[c] = df[c].cat.set_categories(cats)
    if cat_cols:
        for c in cat_cols:
            codes = df[c].cat.codes.to_numpy().astype(np.float64)
            codes[codes == -1] = np.nan  # unseen/missing -> NaN
            df[c] = codes
    names = [str(c) for c in df.columns]
    if categorical_feature == "auto":
        categorical_feature = [names.index(str(c)) for c in unordered]
    mat = np.ascontiguousarray(df.to_numpy(dtype=np.float64))
    return mat, names, categorical_feature, pandas_categorical


class Dataset:
    """Training/validation dataset with lazy construction
    (reference: basic.py:712-1664)."""

    def __init__(self, data, label=None, reference: Optional["Dataset"] = None,
                 weight=None, group=None, init_score=None,
                 feature_name="auto", categorical_feature="auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = True, silent: bool = False):
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params) if params else {}
        self.free_raw_data = free_raw_data
        self._handle: Optional[BinnedDataset] = None
        self.used_indices: Optional[np.ndarray] = None
        self._matrix_cache: Optional[np.ndarray] = None

    # ------------------------------------------------------------------
    def _resolve_categorical(self, names: List[str]) -> List[int]:
        cats = self.categorical_feature
        if cats == "auto" or cats is None:
            cats = self.params.get("categorical_feature", [])
        out = []
        for c in cats or []:
            if isinstance(c, str):
                if c in names:
                    out.append(names.index(c))
            else:
                out.append(int(c))
        return sorted(set(out))

    def construct(self) -> "Dataset":
        """Build the binned representation (reference: _lazy_init,
        basic.py:819)."""
        if self._handle is not None:
            return self
        if self.data is None:
            raise LightGBMError("Cannot construct Dataset: raw data was freed")
        # the root of the data set's set-up spans (Booster.setup_trace),
        # recorded telemetry on or off; the construct path's go under it
        sparse = _is_scipy_sparse(self.data)
        with obs.phase("setup/dataset", record=obs.SetupTrace(),
                       path="from_csr" if sparse else "from_matrix"):
            self._construct()
        if self.free_raw_data:
            self.data = None
        return self

    def _construct(self) -> None:
        self.pandas_categorical = getattr(self, "pandas_categorical", None)
        with obs.phase("convert"):
            if _is_pandas_df(self.data):
                ref_pc = (getattr(self.reference.construct(),
                                  "pandas_categorical", None)
                          if self.reference is not None else None)
                mat, names, auto_cat, self.pandas_categorical = \
                    _data_from_pandas(self.data, self.categorical_feature,
                                      ref_pc)
                if self.categorical_feature == "auto" and auto_cat:
                    # keep "auto" when no category-dtype columns exist so
                    # the params['categorical_feature'] fallback still
                    # applies
                    self.categorical_feature = auto_cat
            elif _is_scipy_sparse(self.data):
                mat = self.data  # stays sparse; from_csr never densifies
                names = None
            else:
                mat = _to_matrix(self.data)
                names = _feature_names_of(self.data)
        if isinstance(self.feature_name, (list, tuple)):
            names = list(self.feature_name)
        if names is None:
            names = [f"Column_{i}" for i in range(mat.shape[1])]
        config = Config.from_params(self.params)
        ref_handle = None
        if self.reference is not None:
            ref_handle = self.reference.construct()._handle
        builder = (BinnedDataset.from_csr if _is_scipy_sparse(mat)
                   else BinnedDataset.from_matrix)
        self._handle = builder(
            mat, config,
            categorical_features=self._resolve_categorical(names),
            feature_names=names, reference=ref_handle)
        if self.label is not None:
            self.set_label(self.label)
        if self.weight is not None:
            self.set_weight(self.weight)
        if self.group is not None:
            self.set_group(self.group)
        if self.init_score is not None:
            self.set_init_score(self.init_score)

    # ------------------------------------------------------------------
    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score,
                       params=params or self.params,
                       free_raw_data=self.free_raw_data)

    # -- field setters/getters (reference: set_field/get_field) --------
    def set_label(self, label) -> None:
        self.label = label
        if self._handle is not None:
            arr = np.asarray(
                label.values if hasattr(label, "values") else label)
            self._handle.metadata.set_label(arr.ravel())

    def set_weight(self, weight) -> None:
        self.weight = weight
        if self._handle is not None and weight is not None:
            self._handle.metadata.set_weights(np.asarray(weight).ravel())

    def set_group(self, group) -> None:
        self.group = group
        if self._handle is not None and group is not None:
            self._handle.metadata.set_query(np.asarray(group).ravel())

    def set_init_score(self, init_score) -> None:
        self.init_score = init_score
        if self._handle is not None and init_score is not None:
            self._handle.metadata.set_init_score(np.asarray(init_score).ravel())

    def get_label(self):
        if self._handle is not None and self._handle.metadata.label is not None:
            return self._handle.metadata.label
        return self.label

    def get_weight(self):
        return self.weight

    def get_group(self):
        return self.group

    def get_init_score(self):
        return self.init_score

    def get_data(self):
        return self.data

    def num_data(self) -> int:
        if self._handle is not None:
            return self._handle.num_data
        if _is_scipy_sparse(self.data):
            return self.data.shape[0]
        return _to_matrix(self.data).shape[0]

    def num_feature(self) -> int:
        if self._handle is not None:
            return self._handle.num_total_features
        if _is_scipy_sparse(self.data):
            return self.data.shape[1]
        return _to_matrix(self.data).shape[1]

    def get_feature_name(self) -> List[str]:
        self.construct()
        return list(self._handle.feature_names)

    def bundle_groups(self) -> List[List[int]]:
        """The columns of the data each physical column of the binned
        matrix holds, one list a physical column, in the order EFB's encoder
        writes them (io/bundling.py: where two members of a list are
        non-default in one row, the LATER one is kept and the earlier reads
        as its default).  One column a list where nothing was bundled;
        columns the binning dropped as constant are in no list."""
        self.construct()
        h = self._handle
        groups = (h.bundle.groups if h.bundle is not None
                  else [[i] for i in range(h.num_features)])
        return [[int(h.real_feature_idx[i]) for i in members]
                for members in groups]

    def categorical_bins(self) -> Dict[int, Dict[str, Any]]:
        """The bin map of each declared categorical column the binning
        kept, by column of the data: ``values``, the category of bin 0, 1,
        ... (count-ordered from the bin-finding sample, io/binning.py; -1 is
        the pseudo-category of missing values), and ``all_kept``: True where
        every value the sample held has a bin of its own and nothing was
        missing.  Where it is False, a value that is not listed (a rare
        category the map dropped, a negative or missing value) shares the
        LAST bin with the category listed there, and that bin is in no
        split's left set (reference: feature_histogram.hpp:130-131,
        ``used_bin``)."""
        from .io.binning import BIN_CATEGORICAL, MISSING_NONE
        self.construct()
        h = self._handle
        out = {}
        for j in h.real_feature_idx:
            m = h.bin_mappers[int(j)]
            if m.bin_type == BIN_CATEGORICAL:
                out[int(j)] = {
                    "values": [int(c) for c in m.bin_2_categorical],
                    "all_kept": m.missing_type == MISSING_NONE}
        return out

    def subset(self, used_indices: Sequence[int], params=None) -> "Dataset":
        """Row-subset view constructed in this dataset's bin space."""
        self.construct()
        if self.data is None:
            raise LightGBMError("Cannot subset: raw data was freed; "
                                "use free_raw_data=False")
        idx = np.asarray(used_indices)
        if self._matrix_cache is None:
            # sparse raw data row-slices sparsely — densifying a wide
            # sparse matrix here would defeat the no-densify CSR path
            self._matrix_cache = (self.data.tocsr()
                                  if _is_scipy_sparse(self.data)
                                  else _to_matrix(self.data))
        sub = Dataset(self._matrix_cache[idx], reference=self,
                      params=params or self.params,
                      free_raw_data=self.free_raw_data)
        if self.label is not None:
            sub.label = np.asarray(self.label)[idx]
        if self.weight is not None:
            sub.weight = np.asarray(self.weight)[idx]
        if self.init_score is not None:
            sub.init_score = np.asarray(self.init_score)[idx]
        if self.group is not None:
            # group sizes of the selected rows: count consecutive query ids
            sizes = np.asarray(self.group).ravel()
            qid = np.repeat(np.arange(len(sizes)), sizes)[idx]
            _, counts = np.unique(qid, return_counts=True)
            sub.group = counts
        sub.used_indices = idx
        return sub

    def save_binary(self, filename: str) -> "Dataset":
        """Serialize the constructed dataset (numpy archive rather than the
        reference's custom binary format; reference: dataset.h:416)."""
        self.construct()
        from .io.dataset_io import save_dataset
        save_dataset(self._handle, filename)
        return self


def _same_bin_mappers(a: BinnedDataset, b: BinnedDataset) -> bool:
    """True when two constructed datasets share bin mappings (reference:
    Dataset::CheckAlign semantics for validation data)."""
    if a.bin_mappers is b.bin_mappers:
        return True
    if len(a.bin_mappers) != len(b.bin_mappers):
        return False
    for ma, mb in zip(a.bin_mappers, b.bin_mappers):
        if (ma.num_bin != mb.num_bin or ma.bin_type != mb.bin_type
                or ma.missing_type != mb.missing_type
                or not np.array_equal(ma.bin_upper_bound, mb.bin_upper_bound)
                or ma.bin_2_categorical != mb.bin_2_categorical):
            return False
    return True


class Booster:
    """Trained model handle + training driver (reference: basic.py:1666+)."""

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None, silent: bool = False):
        self.params = dict(params) if params else {}
        self.best_iteration = -1
        self.best_score: Dict = {}
        self._train_data_name = "training"
        self.train_set = None
        self.valid_sets: List[Dataset] = []
        self._gbdt = None

        if train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError(f"Training data should be Dataset instance, "
                                f"met {type(train_set).__name__}")
            self._init_train(train_set)
        elif model_file is not None:
            from .io.model_io import load_model_file
            self._gbdt, self.config = load_model_file(model_file)
            self.pandas_categorical = getattr(self._gbdt,
                                              "pandas_categorical", None)
        elif model_str is not None:
            from .io.model_io import load_model_string
            self._gbdt, self.config = load_model_string(model_str)
            self.pandas_categorical = getattr(self._gbdt,
                                              "pandas_categorical", None)
        else:
            raise TypeError("Need at least one training dataset or model "
                            "file or model string to create Booster instance")

    # ------------------------------------------------------------------
    def _init_train(self, train_set: Dataset) -> None:
        self.config = Config.from_params(self.params)
        train_set.params = {**train_set.params, **self.params}
        train_set.construct()
        self.train_set = train_set
        # the root of the trainer's set-up spans (Booster.setup_trace),
        # recorded telemetry on or off; GBDT.init's spans go under it
        with obs.phase("setup/booster", record=obs.SetupTrace(),
                       rows=int(train_set._handle.num_data),
                       boosting=str(self.config.boosting)):
            with obs.phase("create"):
                # the first Booster of a process imports the trainer here
                # (boosting -> core.plan -> ops.pallas_hist -> pallas)
                from .boosting import create_boosting
                from .metric import create_metrics
                from .objective import create_objective
                objective = create_objective(self.config)
                metrics = create_metrics(self.config)
                self._gbdt = create_boosting(self.config)
            self._gbdt.init(self.config, train_set._handle, objective,
                            metrics)
        self.pandas_categorical = getattr(train_set, "pandas_categorical",
                                          None)

    def add_valid(self, data: Dataset, name: str) -> "Booster":
        if not isinstance(data, Dataset):
            raise TypeError(f"Validation data should be Dataset instance, "
                            f"met {type(data).__name__}")
        # A valid set must be binned with the TRAINING set's mappers —
        # trees are replayed in bin space, so mismatched mappers silently
        # corrupt validation metrics (reference fails loudly:
        # 'Cannot add validation data, since it has different bin mappers
        # with training data', gbdt.cpp ResetTrainingData analog).
        if data._handle is None:
            if self.train_set is not None:
                data.reference = self.train_set
            data.construct()
        elif (self.train_set is not None and self.train_set._handle is not None
              and not _same_bin_mappers(data._handle,
                                        self.train_set._handle)):
            raise LightGBMError(
                "Cannot add validation data, since it has different bin "
                "mappers with training data; construct it with "
                "reference=train_set")
        self._gbdt.add_valid(data._handle, name)
        self.valid_sets.append(data)
        return self

    # ------------------------------------------------------------------
    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting iteration; True = no further splits possible
        (reference: basic.py:2050, c_api LGBM_BoosterUpdateOneIter)."""
        if train_set is not None and train_set is not self.train_set:
            raise LightGBMError("Resetting the training set is not supported; "
                                "create a new Booster instead")
        if fobj is None:
            return self._gbdt.train_one_iter()
        grad, hess = fobj(self._raw_train_score(), self.train_set)
        return self._gbdt.train_one_iter(np.asarray(grad), np.asarray(hess))

    def work_counters(self, last: Optional[int] = None) -> Dict[str, Any]:
        """What growing the last ``last`` iterations' trees cost, as the
        growth program counted it itself: loop bodies, kernel launches and
        the leaf lanes they filled, rows histogrammed and rows routed
        (``boosting/gbdt.py GBDT.work_counters`` names every key).  The
        counters are part of the one program training runs, telemetry on or
        off; ``update()`` keeps them on the device and this call fetches
        them.  ``counted`` is False where the trainer does not count (a
        loaded model, the XLA growers, CEGB, RF)."""
        fn = getattr(self._gbdt, "work_counters", None)
        if fn is None:
            return {"counted": False, "iterations": [], "trees": []}
        return fn(last)

    def setup_trace(self) -> Dict[str, Any]:
        """Where set-up went, recorded by the program itself, telemetry on
        or off; cheap to call, JSON-able.  ``clock`` is ``unix_s``: every
        ``t`` below is unix seconds on the host's clock.

        ``spans``: one dict a span, by start: ``name``, ``t``, ``dur_s``,
        ``span_id``, ``parent_id``, ``attrs``.  The root ``setup/dataset``
        (``Dataset.construct()`` of the training set) holds ``convert`` (the
        raw table made a float64 matrix), ``sample``, ``bin_find``,
        ``bundle`` (where EFB grouping ran) and ``binarize``;
        the root ``setup/booster`` holds ``objective_init``, ``meta``,
        ``plan``, ``place_bins``, ``build_grower``, ``place_scores`` and
        ``jit_helpers``; an ``update`` span (``attrs.iteration``) stands
        for every ``update()`` during which JAX built or loaded a program,
        and for no other.

        ``programs``: one dict a program JAX built or loaded in this
        process (the newest 256; ``programs_seen`` counts all, and
        ``programs_at_update`` those there were when the newest
        ``update()`` returned: a later one is the caller's own): ``seq``,
        ``fun_name``, ``t``, ``trace_s``, ``lower_s``, ``backend_s``,
        ``cache`` (``hit`` / ``miss`` / ``off``), ``retrieval_s``,
        ``saved_s`` (``obs/trace.py`` names each), and ``parent_id``: the
        ``update`` span it was built under, else the innermost set-up span
        that was open at its start, else None."""
        return self._gbdt.setup_trace()

    def bag_mask(self):
        """The rows the newest iteration's trees were grown on, a bool
        ``[num_data]`` device array (``np.asarray`` fetches it): all True
        where nothing samples, the bag under bagging or GOSS.  Training
        never copies it to the host; this call is where a caller may."""
        fn = getattr(self._gbdt, "bag_mask", None)
        if fn is None:
            raise LightGBMError("bag_mask() needs a training Booster; a "
                                "loaded model has no rows")
        return fn()

    def _raw_train_score(self) -> np.ndarray:
        s = np.asarray(self._gbdt._train_score, dtype=np.float64)
        return s[:, 0] if self._gbdt.num_tpi == 1 else s

    def rollback_one_iter(self) -> "Booster":
        self._gbdt.rollback_one_iter()
        return self

    def refit(self, data, label, decay_rate: float = 0.9,
              **kwargs) -> "Booster":
        """Refit this model's tree structures to new data: leaf outputs
        become ``decay_rate * old + (1 - decay_rate) * new`` (reference:
        basic.py:2547 Booster.refit -> GBDT::RefitTree gbdt.cpp:298-321)."""
        import copy
        if self._gbdt is None or self._gbdt.num_trees == 0:
            raise LightGBMError("Cannot refit an empty model")
        if getattr(self._gbdt, "objective", None) is None:
            raise LightGBMError("Cannot refit due to null objective function.")
        params = dict(self.params or {})
        params["refit_decay_rate"] = decay_rate
        params.update(kwargs)
        # file-loaded boosters carry no params: seed the objective from
        # the model's minimal config, or the refit trainer would compute
        # REGRESSION gradients for a binary/multiclass forest
        if "objective" not in params and self.config is not None:
            params["objective"] = self.config.objective
            if self.config.num_class > 1:
                params["num_class"] = self.config.num_class
        new_set = Dataset(data, label=label, params=params)
        nb = Booster(params=params, train_set=new_set)
        nb._gbdt.load_initial_models(
            [copy.deepcopy(t) for t in self._gbdt.models],
            replay_scores=False)  # refit rebuilds scores from scratch
        nb._gbdt.refit_models(decay_rate)
        return nb

    def current_iteration(self) -> int:
        return self._gbdt.current_iteration()

    def num_trees(self) -> int:
        return self._gbdt.num_trees

    def num_model_per_iteration(self) -> int:
        return self._gbdt.num_tpi

    # ------------------------------------------------------------------
    def eval_train(self, feval=None) -> List:
        return [e for e in self._eval_all(feval)
                if e[0] == self._train_data_name]

    def eval_valid(self, feval=None) -> List:
        return [e for e in self._eval_all(feval, include_train=False)
                if e[0] != self._train_data_name]

    def eval(self, data=None, name=None, feval=None) -> List:
        if data is None:
            return self._eval_all(feval)
        if data is self.train_set:
            return self.eval_train(feval)
        for i, vds in enumerate(self.valid_sets):
            if data is vds:
                want = self._gbdt.valid_names[i]
                return [e for e in self._eval_all(feval) if e[0] == want]
        raise LightGBMError("Can only evaluate the training set or a dataset "
                            "previously attached with add_valid")

    def _eval_all(self, feval=None, include_train: bool = True) -> List:
        out = []
        for ds_name, mname, value, hib in self._gbdt.eval_results(
                include_train=include_train):
            if ds_name == "training":
                ds_name = self._train_data_name
            out.append((ds_name, mname, value, hib))
        if feval is not None:
            def run_feval(score, dataset, tag):
                # custom metrics receive TRANSFORMED predictions, like the
                # reference (feval(self.__inner_predict(i), data) where
                # GetPredict applies the objective's ConvertOutput)
                obj = self._gbdt.objective
                preds = np.asarray(obj.convert_output(score)) \
                    if obj is not None else score
                res = feval(preds, dataset)
                if res is None:
                    return
                entries = res if isinstance(res, list) else [res]
                for (n, v, hb) in entries:
                    out.append((tag, n, v, hb))
            if include_train:
                run_feval(self._raw_train_score(), self.train_set,
                          self._train_data_name)
            for i, vds in enumerate(self.valid_sets):
                s = np.asarray(self._gbdt._valid_scores[i], dtype=np.float64)
                s = s[:, 0] if self._gbdt.num_tpi == 1 else s
                run_feval(s, vds, self._gbdt.valid_names[i])
        return out

    # ------------------------------------------------------------------
    def predict(self, data, num_iteration: Optional[int] = None,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False, start_iteration: int = 0,
                **kwargs) -> np.ndarray:
        if _is_pandas_df(data) and getattr(self, "pandas_categorical",
                                           None) is not None:
            mat, _, _, _ = _data_from_pandas(
                data, categorical_feature=None,
                pandas_categorical=self.pandas_categorical)
        elif _is_scipy_sparse(data):
            if data.shape[1] > self.num_feature():
                # reject BEFORE the block-wise densify below — a too-wide
                # matrix means the caller's feature space is not the
                # model's (the reference C API fails the same way:
                # 'The number of features in data ... is not the same as
                # it was in training data')
                raise LightGBMError(
                    f"The number of features in data ({data.shape[1]}) is "
                    f"not the same as it was in training data "
                    f"({self.num_feature()})")
            if data.shape[1] < self.num_feature():
                # LibSVM-style input sizes by the max feature index
                # PRESENT; pad implicit-zero columns up to the model's
                # feature count (the reference pads the same way)
                import scipy.sparse as sp
                pad = sp.csr_matrix((data.shape[0],
                                     self.num_feature() - data.shape[1]))
                data = sp.hstack([data.tocsr(), pad], format="csr")
            # block-wise densify, ~128MB of dense cells per block: bounded
            # memory on wide sparse inputs (the reference predicts sparse
            # rows natively, predictor.hpp:140-180; row blocks are the
            # dense-core analog)
            block = max(256, (1 << 24) // max(data.shape[1], 1))
            if data.shape[0] > block:
                csr = data.tocsr()
                blocks = [
                    self.predict(csr[i:i + block],
                                 num_iteration=num_iteration,
                                 raw_score=raw_score, pred_leaf=pred_leaf,
                                 pred_contrib=pred_contrib,
                                 start_iteration=start_iteration, **kwargs)
                    for i in range(0, csr.shape[0], block)]
                return np.concatenate(blocks, axis=0)
            mat = _to_matrix(data)
        else:
            mat = _to_matrix(data)
        # sparse input was padded to the model width above (LibSVM-style
        # narrower matrices); anything else must match exactly — the
        # reference C API raises the same error both directions, and a
        # narrower dense matrix would otherwise die in an IndexError
        # deep inside binning
        if mat.shape[1] != self.num_feature():
            raise LightGBMError(
                f"The number of features in data ({mat.shape[1]}) is not "
                f"the same as it was in training data "
                f"({self.num_feature()})")
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration > 0 else -1
        if pred_leaf:
            return self._gbdt.predict_leaf(mat, num_iteration, start_iteration)
        if pred_contrib:
            # routes heavy inputs through the batched device TreeSHAP
            # kernel (explain/) when a device is available; small inputs
            # and count-less models stay on the host oracle (core/shap)
            return self._gbdt.predict_contrib(mat, num_iteration,
                                              start_iteration)
        return self._gbdt.predict(mat, num_iteration, raw_score,
                                  start_iteration)

    # ------------------------------------------------------------------
    def save_model(self, filename: str, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> "Booster":
        with open(filename, "w") as fh:
            fh.write(self.model_to_string(num_iteration, start_iteration))
        # quality-profile sidecar (obs/drift.py): boosters that still
        # hold their training dataset persist the reference
        # distribution beside the model so serving can arm drift
        # monitoring; the model text format itself stays untouched
        # (reference-compatible).  Never lets profiling fail a save.
        cfg = getattr(self._gbdt, "config", None) if self._gbdt else None
        if (self._gbdt is not None
                and getattr(self._gbdt, "train_ds", None) is not None
                and (cfg is None
                     or getattr(cfg, "tpu_quality_profile", True))):
            from .obs.drift import profile_path
            try:
                prof = self._gbdt.quality_profile()
                if prof is not None:
                    prof.save(profile_path(filename))
            except Exception as exc:  # noqa: BLE001
                log.warning("quality profile sidecar skipped: %s", exc)
        return self

    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0) -> str:
        from .io.model_io import model_to_string
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration > 0 else -1
        txt = model_to_string(self._gbdt, num_iteration, start_iteration)
        pc = getattr(self, "pandas_categorical", None)
        if pc is not None:
            # appended like the reference python package so string/file
            # round-trips keep the category->code mapping
            # (reference: basic.py:367 _dump_pandas_categorical); omitted
            # when absent to stay byte-identical with reference CLI files
            import json as _json

            from .compat import json_default_with_numpy
            txt += ("\npandas_categorical:"
                    + _json.dumps(pc, default=json_default_with_numpy)
                    + "\n")
        return txt

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> Dict[str, Any]:
        """Model as a nested dict, the reference's JSON dump structure
        (reference: GBDT::DumpModel, gbdt_model_text.cpp:20-85; python
        Booster.dump_model, basic.py:2243)."""
        from .io.model_json import dump_model
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration > 0 else -1
        return dump_model(self._gbdt, num_iteration, start_iteration)

    def model_to_if_else(self, num_iteration: Optional[int] = None) -> str:
        """Standalone C scoring code for the forest (reference:
        GBDT::ModelToIfElse, gbdt_model_text.cpp:88-270 — the CLI
        ``task=convert_model`` output)."""
        from .io.model_json import model_to_if_else
        return model_to_if_else(self._gbdt, num_iteration)

    def feature_importance(self, importance_type: str = "split",
                           iteration=None) -> np.ndarray:
        return self._gbdt.feature_importance(
            importance_type, num_iteration=-1 if iteration is None
            else int(iteration))

    def feature_name(self) -> List[str]:
        if self._gbdt.train_ds is not None:
            return list(self._gbdt.train_ds.feature_names)
        return list(getattr(self._gbdt, "feature_names", []))

    def num_feature(self) -> int:
        if self._gbdt.train_ds is not None:
            return self._gbdt.train_ds.num_total_features
        return len(self.feature_name())

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        self.params.update(params)
        self.config.update(params)
        if self._gbdt is not None:
            # file-loaded boosters start with config=None; adopting the
            # updated Booster config is what lets prediction-time knobs
            # (pred_early_stop*) reach them
            self._gbdt.config = self.config
            if self._gbdt.train_ds is not None:
                self._gbdt.shrinkage_rate = float(self.config.learning_rate)
        return self

    def free_dataset(self) -> "Booster":
        self.train_set = None
        return self

    # ------------------------------------------------------------------
    # pickling / copying: serialize through the model text, like the
    # reference Booster's __getstate__ (reference: basic.py:1875-1904 —
    # the handle cannot cross processes; the model string can). The
    # unpickled booster is prediction-ready; training state is not
    # carried (same as the reference unless free_raw_data=False).
    def __getstate__(self) -> Dict[str, Any]:
        state = {"params": self.params,
                 "best_iteration": self.best_iteration,
                 "best_score": self.best_score,
                 "model_str": self.model_to_string(num_iteration=-1)}
        return state

    def __setstate__(self, state: Dict[str, Any]) -> None:
        from .io.model_io import load_model_string
        self.params = state.get("params", {})
        self.best_iteration = state.get("best_iteration", -1)
        self.best_score = state.get("best_score", {})
        self._train_data_name = "training"
        self.train_set = None
        self.valid_sets = []
        self._gbdt, self.config = load_model_string(state["model_str"])
        self.pandas_categorical = getattr(self._gbdt, "pandas_categorical",
                                          None)

    def __copy__(self) -> "Booster":
        return self.__deepcopy__(None)

    def __deepcopy__(self, memo) -> "Booster":
        new = Booster(model_str=self.model_to_string(num_iteration=-1))
        new.params = dict(self.params)
        new.best_iteration = self.best_iteration
        return new

    def get_split_value_histogram(self, feature, bins=None,
                                  xgboost_style: bool = False):
        """Histogram of split threshold values used for ``feature`` across
        the forest (reference: basic.py:2583 Booster.
        get_split_value_histogram)."""
        if isinstance(feature, str):
            feature = self.feature_name().index(feature)
        values = []
        for tree in self._gbdt.models:
            nn = max(tree.num_leaves - 1, 0)
            for i in range(nn):
                if (int(tree.split_feature[i]) == feature
                        and not tree.is_categorical(i)):
                    values.append(float(tree.threshold[i]))
        values = np.asarray(values, np.float64)
        if bins is None or (isinstance(bins, int) and bins > len(values)):
            bins = max(len(values), 1)
        hist, edges = np.histogram(values, bins=bins)
        if not xgboost_style:
            return hist, edges
        import pandas as pd
        mask = hist != 0
        return pd.DataFrame({"SplitValue": edges[1:][mask],
                             "Count": hist[mask]})

    def trees_to_dataframe(self):
        """One row per node/leaf of every tree (reference: basic.py:2757
        Booster.trees_to_dataframe)."""
        import pandas as pd
        names = self.feature_name()
        rows = []

        def walk(tree, ti, node, depth, parent):
            if node >= 0:  # internal
                idx = f"{ti}-S{node}"
                f = int(tree.split_feature[node])
                rows.append(dict(
                    tree_index=ti, node_depth=depth, node_index=idx,
                    left_child=_child_name(tree, ti, tree.left_child[node]),
                    right_child=_child_name(tree, ti, tree.right_child[node]),
                    parent_index=parent,
                    split_feature=names[f] if f < len(names) else str(f),
                    split_gain=float(tree.split_gain[node]),
                    threshold=float(tree.threshold[node]),
                    decision_type="==" if tree.is_categorical(node)
                    else "<=",
                    missing_direction="left"
                    if (tree.decision_type[node] & 2) else "right",
                    value=float(tree.internal_value[node]),
                    weight=float(tree.internal_weight[node]),
                    count=int(tree.internal_count[node])))
                walk(tree, ti, int(tree.left_child[node]), depth + 1, idx)
                walk(tree, ti, int(tree.right_child[node]), depth + 1, idx)
            else:
                leaf = ~node
                rows.append(dict(
                    tree_index=ti, node_depth=depth,
                    node_index=f"{ti}-L{leaf}", left_child=None,
                    right_child=None, parent_index=parent,
                    split_feature=None, split_gain=None, threshold=None,
                    decision_type=None, missing_direction=None,
                    value=float(tree.leaf_value[leaf]),
                    weight=float(tree.leaf_weight[leaf]),
                    count=int(tree.leaf_count[leaf])))

        def _child_name(tree, ti, child):
            return f"{ti}-S{child}" if child >= 0 else f"{ti}-L{~child}"

        for ti, tree in enumerate(self._gbdt.models):
            walk(tree, ti, 0 if tree.num_leaves > 1 else ~0, 1, None)
        return pd.DataFrame(rows)

    def free_network(self) -> "Booster":
        from .parallel.distributed import shutdown
        shutdown()  # tears down jax.distributed AND resets NETWORK
        return self

    def set_network(self, machines, local_listen_port: int = 12400,
                    listen_time_out: int = 120, num_machines: int = 1) -> "Booster":
        """Record the machine topology; like the reference, the network
        itself comes up when a Booster binds to training data — here via
        ``parallel.distributed.init_distributed`` (jax.distributed) instead
        of the reference's TCP linkers (reference: basic.py set_network ->
        Network::Init, network.cpp:24-74)."""
        from .parallel import mesh as _mesh
        from .parallel.distributed import parse_machine_list
        if not isinstance(machines, str):
            machines = ",".join(str(m) for m in machines)
        hosts = parse_machine_list(machines, default_port=local_listen_port)
        _mesh.NETWORK.update(machines=",".join(hosts),
                             num_machines=int(num_machines),
                             local_listen_port=int(local_listen_port),
                             time_out=int(listen_time_out))
        return self
